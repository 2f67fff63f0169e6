"""Scan-over-rounds drivers (train.fit).

``federated_fit`` over R rounds must (a) be numerically identical to R
sequential ``federated_round`` calls with the same per-round keys and
round indices (the scan threads the round counter into the mask-draw
words), and (b) trace the round body exactly once regardless of R —
one compile per (R, K, E, batch) shape, with re-dispatch free of
retracing.  ``sharded_client_fit`` is the same contract inside
``shard_map`` on the forced 4-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm import shard_map
from repro.core import FederatedConfig, ZamplingConfig, build_specs, init_state
from repro.core.federated import (
    WIRE_METRIC_KEYS,
    federated_round,
    sharded_client_update,
)
from repro.data import client_batch_stream, iid_client_split, make_teacher_dataset
from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss
from repro.train import federated_fit, sharded_client_fit

K, E, B = 4, 2, 32


@pytest.fixture(scope="module")
def setup():
    ds = make_teacher_dataset(n_train=600, n_test=100, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=2.0, d=5, window=128, min_size=256))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    clients = iid_client_split(ds, K)
    stream = client_batch_stream(clients, B, E, seed=0)
    return zspecs, state, stream


def _round_stack(stream, r):
    xs, ys = zip(*(next(stream) for _ in range(r)))
    return {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}


def test_fit_matches_sequential_rounds(setup):
    zspecs, state, stream = setup
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                          aggregate="psum_u32")
    R = 5
    batches = _round_stack(stream, R)
    key = jax.random.PRNGKey(7)
    st_fit, mets = jax.jit(
        lambda s, b, k: federated_fit(zspecs, s, mlp_loss, b, k, cfg)
    )(state, batches, key)
    assert mets["loss"].shape == (R,)
    for mk in WIRE_METRIC_KEYS:
        assert mets[mk].shape == (R,)

    round_fn = jax.jit(
        lambda s, b, k, r: federated_round(zspecs, s, mlp_loss, b, k, cfg,
                                           round_index=r)
    )
    st_seq = state
    seq_losses = []
    for r, sub in enumerate(jax.random.split(key, R)):
        b = jax.tree.map(lambda x, r=r: x[r], batches)
        st_seq, m = round_fn(st_seq, b, sub, jnp.uint32(r))
        seq_losses.append(float(m["loss"]))
    for p in st_fit["scores"]:
        np.testing.assert_array_equal(
            np.asarray(st_fit["scores"][p]), np.asarray(st_seq["scores"][p])
        )
    for p in st_fit["dense"]:
        np.testing.assert_allclose(
            np.asarray(st_fit["dense"][p]), np.asarray(st_seq["dense"][p]),
            rtol=1e-6, atol=1e-7,
        )
    np.testing.assert_allclose(np.asarray(mets["loss"]), seq_losses,
                               rtol=1e-6, atol=1e-7)


def test_fit_compiles_once(setup):
    """The loss is Python-traced a fixed number of times per COMPILE,
    never per round: R=5 and R=2 fits trace identically, and a second
    same-shape call adds zero traces."""
    zspecs, state, stream = setup
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1)
    traces = []

    def counting_loss(params, batch):
        traces.append(1)
        return mlp_loss(params, batch)

    def fit(r):
        f = jax.jit(lambda s, b, k: federated_fit(
            zspecs, s, counting_loss, b, k, cfg))
        b = _round_stack(stream, r)
        out = f(state, b, jax.random.PRNGKey(0))
        jax.block_until_ready(out)
        return f, b

    f5, b5 = fit(5)
    n5 = len(traces)
    assert n5 > 0
    f5(state, b5, jax.random.PRNGKey(1))  # same shapes: cached
    assert len(traces) == n5, "same-shape refit retraced"
    traces.clear()
    fit(2)
    n2 = len(traces)
    assert n2 == n5, (
        f"trace count scales with R ({n2} at R=2 vs {n5} at R=5): "
        "the scan driver is not compiling once"
    )


def test_fit_respects_rounds_arg(setup):
    zspecs, state, stream = setup
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1)
    batches = _round_stack(stream, 3)
    _, mets = jax.jit(lambda s, b, k: federated_fit(
        zspecs, s, mlp_loss, b, k, cfg, rounds=3))(
        state, batches, jax.random.PRNGKey(0))
    assert mets["loss"].shape == (3,)


def _data_mesh(size=4):
    return data_mesh_or_skip(size)


def test_sharded_fit_matches_sequential(setup):
    """R rounds scanned INSIDE shard_map == R sequential shard_map
    dispatches of sharded_client_update (exact), packed transport."""
    mesh = _data_mesh()
    zspecs, state, stream = setup
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                          aggregate="allgather_packed")
    R = 3
    per_round = [next(stream) for _ in range(R)]
    # per-shard slab: (K, R, E, B, ...) — K is the sharded mesh axis
    rb = {"x": jnp.asarray(np.stack([x for x, _ in per_round], 1)),
          "y": jnp.asarray(np.stack([y for _, y in per_round], 1))}
    key = jax.random.PRNGKey(3)
    state_specs = jax.tree.map(lambda _: P(), state)
    met_specs = round_metric_specs()

    def fit_body(s, b, k):
        b = jax.tree.map(lambda x: x[0], b)  # (R, E, B, ...)
        return sharded_client_fit(zspecs, s, mlp_loss, b, k, cfg)

    with jax.set_mesh(mesh):
        f = shard_map(fit_body, ("data",),
                      (state_specs, P("data"), P()),
                      (state_specs, met_specs))
        st_fit, mets = jax.jit(f)(state, rb, key)
    assert mets["loss"].shape == (R,)

    def round_body(s, b, k, r):
        b = jax.tree.map(lambda x: x[0], b)
        return sharded_client_update(zspecs, s, mlp_loss, b, k, cfg,
                                     round_index=r)

    st_seq = state
    for r, sub in enumerate(jax.random.split(key, R)):
        with jax.set_mesh(mesh):
            f2 = shard_map(round_body, ("data",),
                           (state_specs, P("data"), P(), P()),
                           (state_specs, met_specs))
            b = jax.tree.map(lambda x, r=r: x[:, r], rb)
            st_seq, _ = jax.jit(f2)(st_seq, b, sub, jnp.uint32(r))
    for p in st_fit["scores"]:
        np.testing.assert_array_equal(
            np.asarray(st_fit["scores"][p]), np.asarray(st_seq["scores"][p])
        )
