"""Inputs and weights of the federated cells, made on the device from
the seed.

The classification data follows the repository's offline stand-in for
MNIST (10 class prototypes on the unit sphere in 784 dimensions,
scaled by 1.5, plus Gaussian jitter of 0.35), but every call gets
fresh rows drawn from ``fold_in(data key, call)``, so no two checked
calls share a row and the same seed gives the same rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key from a seed of any size (the low and high 32 bits
    both count)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def stream_keys(key):
    """Independent keys for the weights, the data and the calls."""
    return {name: jax.random.fold_in(key, i)
            for i, name in enumerate(("weights", "data", "calls"))}


def initial_state(zspecs, template, key):
    """The u8 broadcast words (uniform over the 256 codes, i.e. scores
    ~ U(0, 1) as the paper initialises them) and zero biases, made in
    one jitted call."""
    leaves = dict(
        ("/".join(str(getattr(k, "key", k)) for k in path), leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(template)[0])
    sizes = {p: s.n for p, s in zspecs.specs.items()}
    dense = {p: (leaves[p].shape, leaves[p].dtype)
             for p in zspecs.dense_paths}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(sizes))
        words = {p: jax.random.randint(k, (n,), 0, 256, jnp.int32).astype(
            jnp.uint8) for k, (p, n) in zip(ks, sorted(sizes.items()))}
        return {"scores": words,
                "dense": {p: jnp.zeros(s, d) for p, (s, d) in dense.items()}}

    return make(key)


def batch_fn(key, dim: int, classes: int, lead, mesh=None):
    """``gen(call) -> {"x": (*lead, dim) f32, "y": (*lead,) int32}``,
    jitted once for every seed (the key is an argument, not a
    constant); with ``mesh`` the leading axis is laid out over its
    ``data`` axis."""
    pkey = jax.random.fold_in(key, 0x7FFFFFFF)
    protos = jax.random.normal(pkey, (classes, dim), jnp.float32)
    protos = protos / jnp.linalg.norm(protos, axis=1, keepdims=True)
    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        s = NamedSharding(mesh, P("data"))
        out_shardings = {"x": s, "y": s}

    def make(key, protos, c):
        kc = jax.random.fold_in(key, c)
        ky, kx = jax.random.split(kc)
        y = jax.random.randint(ky, lead, 0, classes, jnp.int32)
        noise = jax.random.normal(kx, (*lead, dim), jnp.float32)
        return {"x": 1.5 * protos[y] + 0.35 * noise, "y": y}

    make = jax.jit(make, out_shardings=out_shardings)
    return lambda c: make(key, protos, jnp.uint32(c))
