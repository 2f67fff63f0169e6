"""Plain float32 reference of the federated MNIST-FC round.

Written from the protocol's description, with nothing taken from the
system under test:

  w = Q · Bern(f(s)),  f(s) = clip(s, 0, 1)

Q (one per weight tensor) has ``d`` edges per row.  Row ``r`` of a
tensor reads only the z-window ``r // rows_per_window``; edge ``k``
sits at in-window column ``(base + k·stride) mod window`` with a
Gaussian value of standard deviation ``sqrt(6 / (d·fan_in))``.  Every
index, value and mask bit comes from a counter hash of
(seed, tensor id, ...), so the reference regenerates Q and the draws
itself.  ``w = Q z`` sums each row's edges in ascending slot order,
the order the protocol fixes for every path, so float32 gives the same
bits as any faithful implementation; each slot's ``z[idx]`` is picked
by a product with a 0/1 matrix, exact at any precision.  The transpose
``Qᵀ g`` is an einsum against Q held as dense per-window blocks
``(num_windows, rows_per_window, window)``.

One round: every client decodes the u8 broadcast to probabilities,
takes E SGD steps on its scores (a fresh mask draw per forward pass,
straight-through gradient ``Qᵀ ∇w`` through the clip), draws its
upload bits, and the server averages the bits, averages the dense
leaves, and re-encodes the mean as dithered u8 words.

``precision``: ``"highest"`` is float32 throughout (the reference);
``"high"`` rounds Q's values and each operand of every other
contraction to 16 significant bits first, what a three-pass bfloat16
product keeps of a float32 (the control, the same on every backend).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

M32 = 0xFFFFFFFF
MASK_CTR = 0x0008_0000
DITHER_CTR = 0x0010_0000
CTR_BASE = 0x0001_0000
CTR_STRIDE = 0x0002_0000
CTR_VAL = 0x0004_0000


# --------------------------------------------------------------------------
# the counter hash (murmur3 finalizer over a running combine)
# --------------------------------------------------------------------------

def _u32(x):
    return jnp.asarray(x).astype(jnp.uint32)


def fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def hash_words(*words):
    h = jnp.uint32(0x2545F491)
    for w in words:
        m = fmix32(_u32(w) + jnp.uint32(0x9E3779B9))
        h = (h ^ m) * jnp.uint32(0x165667B1) + jnp.uint32(0x9E3779B9)
    return fmix32(h)


def uniform(u):
    """24 high bits -> f32 in (0, 1]."""
    inv = np.float32(1.0 / (1 << 24))
    return (u >> 8).astype(jnp.int32).astype(jnp.float32) * inv + inv


def key_word(key):
    data = jax.random.key_data(key) if jnp.issubdtype(
        jnp.asarray(key).dtype, jax.dtypes.prng_key) else jnp.asarray(key)
    data = data.astype(jnp.uint32).reshape(-1)
    return hash_words(*(data[i] for i in range(data.shape[0])))


@jax.custom_jvp
def round16(x):
    """Round f32 to 16 significant bits (nearest, ties away); the
    gradient passes straight through."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x80)) & jnp.uint32(0xFFFFFF00)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


round16.defjvps(lambda t, ans, x: t)


def contract(spec, a, b, precision):
    """einsum at float32, or with both operands rounded (control)."""
    if precision == "high":
        a, b = round16(a), round16(b)
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision="highest")


# --------------------------------------------------------------------------
# Q per tensor
# --------------------------------------------------------------------------

def tensor_layout(shape, tensor_id, *, compression, d, window, seed):
    """Static sizes of one tensor's Q."""
    m = int(math.prod(shape))
    n_raw = max(1, math.ceil(m / compression))
    window = int(min(window, 1 << max(1, math.ceil(math.log2(max(n_raw, 2))))))
    if d >= window:
        d = max(1, window // 2)
    nw = max(1, math.ceil(n_raw / window))
    rpw = math.ceil(m / nw)
    fan_in = int(math.prod(shape[:-1])) if len(shape) > 1 else int(shape[0])
    return {"shape": tuple(shape), "tensor_id": tensor_id, "m": m,
            "n": nw * window, "d": d, "window": window, "num_windows": nw,
            "rows_per_window": rpw, "seed": seed,
            "sigma": np.float32(math.sqrt(6.0 / (d * max(fan_in, 1))))}


def model_layout(cfg):
    """Every leaf of the MLP in flattened (sorted-key) order: zampled
    kernels get a Q layout, biases stay dense.  The tensor id is the
    leaf's position in that order."""
    dims = cfg["dims"]
    z = cfg["zampling"]
    leaves = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        leaves.append((f"layer{i}/bias", (b,)))
        leaves.append((f"layer{i}/kernel", (a, b)))
    leaves.sort(key=lambda pl: pl[0])
    zampled, dense = {}, {}
    for tid, (path, shape) in enumerate(leaves):
        if len(shape) >= 2 and math.prod(shape) >= z["min_size"]:
            zampled[path] = tensor_layout(
                shape, tid, compression=z["compression"], d=z["d"],
                window=z["window"], seed=z["seed"])
        else:
            dense[path] = shape
    return zampled, dense


def q_tables(lay):
    """Q of one tensor, rows padded to num_windows·rows_per_window:
    ``value`` (rows, d) of each edge slot, ``pick`` (d, num_windows,
    window, rows_per_window) the 0/1 selection of each slot's z
    coordinate within the row's window, and ``blocks`` the same Q as
    dense per-window blocks (num_windows, rows_per_window, window).
    Padded rows have zero values."""
    nw, rpw, win, d = (lay["num_windows"], lay["rows_per_window"],
                       lay["window"], lay["d"])
    rows = jnp.arange(nw * rpw, dtype=jnp.uint32)
    seed, tid = lay["seed"], lay["tensor_id"]
    base = hash_words(seed, tid, rows, CTR_BASE) & jnp.uint32(win - 1)
    stride = (hash_words(seed, tid, rows, CTR_STRIDE)
              % jnp.uint32(win // 2)) * jnp.uint32(2) + jnp.uint32(1)
    live = rows < lay["m"]
    two_pi = np.float32(6.283185307179586)
    idx, val = [], []
    for k in range(d):
        idx.append(((base + stride * jnp.uint32(k)) & jnp.uint32(win - 1)
                    ).astype(jnp.int32))
        ua = hash_words(seed, tid, rows, CTR_VAL + 2 * k)
        ub = hash_words(seed, tid, rows, CTR_VAL + 2 * k + 1)
        g = (jnp.sqrt(-2.0 * jnp.log(uniform(ua)))
             * jnp.cos(two_pi * uniform(ub))) * lay["sigma"]
        val.append(jnp.where(live, g, 0.0))
    idx, val = jnp.stack(idx, 1), jnp.stack(val, 1)
    cols = jnp.arange(win, dtype=jnp.int32)
    # slot k's selection as a (window, rows) 0/1 matrix per window: a
    # product with the 0/1 mask picks z[idx] exactly at any precision
    pick = jnp.stack([(cols[:, None] == idx[:, k][None, :]).astype(
        jnp.bfloat16).reshape(win, nw, rpw).transpose(1, 0, 2)
        for k in range(d)])
    blocks = sum(jnp.where(cols[None, :] == idx[:, k:k + 1], val[:, k:k + 1],
                           0.0) for k in range(d))
    return {"pick": pick, "value": val,
            "blocks": blocks.reshape(nw, rpw, win)}


def mask_bits(p, lay, word):
    """Bern(p) draws of one tensor at draw word ``word``: p (..., n)."""
    coords = jnp.arange(lay["n"], dtype=jnp.uint32)
    u = hash_words(lay["seed"], lay["tensor_id"], MASK_CTR,
                   jnp.asarray(word)[..., None], coords)
    return (uniform(u) <= p).astype(jnp.float32)


def reconstruct(q, z, lay, precision):
    """w = Q z for one client, each row's d edges summed in ascending
    slot order: z (n,) 0/1 -> weights in the tensor's shape."""
    nw, win = lay["num_windows"], lay["window"]
    val = round16(q["value"]) if precision == "high" else q["value"]
    zw = z.reshape(nw, win).astype(jnp.bfloat16)
    w = None
    for k in range(lay["d"]):
        sel = jnp.einsum("wc,wcr->wr", zw, q["pick"][k],
                         preferred_element_type=jnp.float32).reshape(-1)
        term = val[:, k] * sel
        w = term if w is None else w + term
    return w[:lay["m"]].reshape(lay["shape"])


def transpose(q, g, lay, precision, block):
    """Qᵀ g for one client: weight cotangent -> (n,).  Each z
    coordinate adds its incoming edges ``q·g`` one source row at a
    time, in ascending row order within each block of ``block`` rows
    of its window, then adds the block sums in block order.  A row
    holds at most one edge into a coordinate, so the dense product
    ``blocks * g`` has that edge's term or an exact zero."""
    nw, rpw, win = lay["num_windows"], lay["rows_per_window"], lay["window"]
    nblk = -(-rpw // block)
    pad = nblk * block - rpw
    qd = jnp.pad(q["blocks"], ((0, 0), (0, pad), (0, 0)))
    gp = jnp.pad(g.reshape(-1), (0, nw * rpw - lay["m"])).reshape(nw, rpw)
    gp = jnp.pad(gp, ((0, 0), (0, pad)))
    if precision == "high":
        qd, gp = round16(qd), round16(gp)
    # row r of every block at once: (block, nw, nblk, win) and (block, nw, nblk)
    qd = qd.reshape(nw, nblk, block, win).transpose(2, 0, 1, 3)
    gp = gp.reshape(nw, nblk, block).transpose(2, 0, 1)

    def add_row(acc, row):
        q_r, g_r = row
        return acc + q_r * g_r[..., None], None

    acc, _ = jax.lax.scan(add_row, jnp.zeros((nw, nblk, win), jnp.float32),
                          (qd, gp))
    gz = jnp.zeros((nw, win), jnp.float32)
    for j in range(nblk):
        gz = gz + acc[:, j]
    return gz.reshape(-1)


def decode_u8(q):
    a = q.astype(jnp.uint32) << 16
    return (a + a // jnp.uint32(255)).astype(jnp.float32) * np.float32(
        1.0 / (1 << 24))


def encode_u8(p, lay, word):
    coords = jnp.arange(lay["n"], dtype=jnp.uint32)
    u = hash_words(lay["seed"], lay["tensor_id"], DITHER_CTR, word, coords)
    dither = (u >> 8).astype(jnp.float32) * np.float32(1.0 / (1 << 24))
    p = jnp.clip(p, 0.0, 1.0)
    q = jnp.floor(p * np.float32(255.0) + np.float32(0.25)
                  + np.float32(0.5) * dither)
    return jnp.clip(q, 0.0, 255.0).astype(jnp.uint8)


# --------------------------------------------------------------------------
# the round
# --------------------------------------------------------------------------

def mlp_loss(params, x, y, n_layers, precision):
    h = x
    for i in range(n_layers):
        h = contract("bi,io->bo", h, params[f"layer{i}/kernel"],
                     precision) + params[f"layer{i}/bias"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    logp = jax.nn.log_softmax(h)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def make_round(cfg, traffic, precision="highest"):
    """Returns (round_fn, layouts, dense_shapes, qtables_fn).

    ``round_fn(qs, words, dense, xs, ys, key, round_index)`` runs one
    round over K clients: xs (K, E, B, 784), ys (K, E, B).  Returns
    (new_words, new_dense, mean_loss)."""
    zl, dense_shapes = model_layout(cfg)
    block = cfg["transpose_block"]
    n_layers = len(cfg["dims"]) - 1
    lr = np.float32(cfg["federated"]["local_lr"])
    E = traffic["local_steps"]
    K = traffic["clients"]
    one_per_chip = traffic["placement"] == "shard_map"

    def one_client(qs, p0, dense0, xs, ys, cword):

        def make_sample(path):
            lay, q = zl[path], qs[path]

            @jax.custom_vjp
            def sampled(p, z):
                return reconstruct(q, z, lay, precision)

            def fwd(p, z):
                return reconstruct(q, z, lay, precision), None

            def bwd(_, g):
                return transpose(q, g, lay, precision, block), None

            sampled.defvjp(fwd, bwd)
            return sampled

        samplers = {p: make_sample(p) for p in zl}

        def loss_of(train, x, y, word):
            params = dict(train["dense"])
            for path, lay in zl.items():
                p = jnp.clip(train["scores"][path], 0.0, 1.0)
                z = mask_bits(jax.lax.stop_gradient(p), lay, word)
                params[path] = samplers[path](p, z)
            return mlp_loss(params, x, y, n_layers, precision)

        def step(train, xe):
            x, y, e = xe
            loss, g = jax.value_and_grad(loss_of)(
                train, x, y, hash_words(cword, e))
            train = jax.tree.map(lambda t, gg: t + (-lr) * gg, train, g)
            return train, loss

        train0 = {"scores": p0, "dense": dense0}
        train, losses = jax.lax.scan(
            step, train0, (xs, ys, jnp.arange(E, dtype=jnp.uint32)))
        up_word = hash_words(cword, jnp.uint32(E))
        bits = {path: mask_bits(jnp.clip(train["scores"][path], 0.0, 1.0),
                                lay, up_word)
                for path, lay in zl.items()}
        return bits, train["dense"], jnp.mean(losses)

    def round_fn(qs, words, dense, xs, ys, key, round_index):
        kw = key_word(key)
        rid = jnp.asarray(round_index).astype(jnp.uint32)
        cwords = hash_words(kw, rid, jnp.arange(K, dtype=jnp.uint32))
        p0 = {path: decode_u8(words[path]) for path in zl}
        if one_per_chip:  # each client alone, as on its own chip
            bits, dense_k, losses = jax.lax.map(
                lambda a: one_client(qs, p0, dense, *a), (xs, ys, cwords))
        else:
            bits, dense_k, losses = jax.vmap(
                one_client, in_axes=(None, None, None, 0, 0, 0))(
                    qs, p0, dense, xs, ys, cwords)
        enc_word = hash_words(kw, rid)
        new_words = {}
        for path, lay in zl.items():
            counts = jnp.sum(bits[path].astype(jnp.uint32), axis=0)
            new_words[path] = encode_u8(counts.astype(jnp.float32) / K,
                                        lay, enc_word)
        new_dense = {p: jnp.mean(v, axis=0) for p, v in dense_k.items()}
        return new_words, new_dense, jnp.mean(losses)

    def qtables():
        return {path: q_tables(lay) for path, lay in zl.items()}

    return round_fn, zl, dense_shapes, qtables
