"""Distribution-aware reconstruction: shard_map over the 'model' axis.

GSPMD cannot partition the scatter in ``grad_z = Q^T grad_w``, and a
flat-row-sharded weight must be RESHARDED to its consumer layout — an
all-gather of the full tensor through a replicated f32 intermediate
(measured 14 GB/device/tensor on qwen3-14b).  Both problems disappear
with the sharding-major layout (QSpec.major_axis/shard_count):

 - shard k owns rows [k·m_pad_loc, (k+1)·m_pad_loc) which read ONLY its
   own ``nw_loc`` z windows — the gather/scatter is purely local;
 - those rows ARE the k-th block of the tensor's sharded axis, so the
   local reshape+moveaxis emits the weight block in consumer layout and
   ``out_specs`` reassembles the global tensor with ZERO collectives.

The shard_map is entered without an explicit mesh
(``repro.comm.shardmap.shard_map``) so it composes with the context
mesh: the caller's ``jax.set_mesh``, or the partially-manual mesh of a
sharded federated round.  Forced-multi-device CPU runs the same path.

Batched variants (``sharded_reconstruct_batched`` /
``sharded_grad_z_batched``): K stacked clients share one generation of
the chunk's hash-RNG indices/values; z rides as a (K, n_loc) slab per
shard and the per-chunk temporaries stay bounded at
O(rpc·d + K·rpc) — the chunk count scales with K so the budget in
TARGET_CHUNK_BYTES holds for any K.

Transpose path: ``sharded_grad_z`` / ``sharded_grad_z_batched``
dispatch plan-vs-scatter like the global ref path
(``core.transpose_plan.resolve_bwd_path``, env ``REPRO_BWD_PLAN``).
The cached transpose plan is shard-local BY CONSTRUCTION: all edges
into window ``w``'s coordinates come from window ``w``'s rows, and the
sharding-major layout gives each shard a contiguous block of windows —
so the (num_windows, window, deg) plan slabs enter the shard_map as
operands sharded ``P('model')`` on the window axis and each shard
gathers purely locally (zero collectives, same as the forward).
Window-chunking (``lax.map``) keeps per-chunk temporaries inside
TARGET_CHUNK_BYTES; the scatter chunks stay as the bit-exactness
oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm.shardmap import shard_map
from ..core.qspec import QSpec, edge_sum, row_indices, row_values
from ..core.transpose_plan import (
    build_transpose_plan,
    plan_window_apply,
    resolve_bwd_path,
)

AXIS = "model"


TARGET_CHUNK_BYTES = 128 << 20  # bound the (rows, d) temporaries


def _shard_map(f, in_specs, out_specs):
    """``comm.shardmap.shard_map`` bound to this module's 'model' axis."""
    return shard_map(f, (AXIS,), in_specs, out_specs)


def _num_chunks(spec: QSpec, nclients: int = 1) -> int:
    per_row = spec.d * 4 * 3 + nclients * 4  # idx/vals/gather + K outputs
    return max(1, min(spec.m_pad_loc,
                      (spec.m_pad_loc * per_row) // TARGET_CHUNK_BYTES))


def _chunk_live_rows(spec: QSpec, c, rpc):
    """Clamped shard-local row ids for chunk ``c`` + their live mask
    (the tail chunk repeats row m_pad_loc-1; its updates are zeroed)."""
    loc = c * rpc + jnp.arange(rpc, dtype=jnp.int32)
    rows = jnp.minimum(loc, spec.m_pad_loc - 1)
    return rows, (loc < spec.m_pad_loc).astype(jnp.float32)


def _chunk_rows(spec: QSpec, c, rpc):
    """Gather indices + values for rows [c*rpc, (c+1)*rpc) of this shard."""
    sid = jax.lax.axis_index(AXIS)
    loc, _ = _chunk_live_rows(spec, c, rpc)
    rp = (sid * spec.m_pad_loc + loc).astype(jnp.uint32)
    idx = row_indices(spec, rp)  # (rpc, d) in-window
    vals = row_values(spec, rp, dtype=jnp.float32)
    win_loc = jnp.minimum(loc // spec.rows_per_window, spec.nw_loc - 1)
    gidx = win_loc[:, None] * spec.window + idx  # local z-slice index
    return gidx, vals


def _check(spec: QSpec, ms: int):
    if spec.shard_count != ms:
        raise ValueError(
            f"spec.shard_count={spec.shard_count} != model axis size {ms}; "
            "build specs with shard_count=model_size"
        )


def _out_spec(spec: QSpec) -> P:
    dims = [None] * len(spec.shape)
    dims[spec.major_axis] = AXIS
    return P(*dims)


def _out_spec_b(spec: QSpec) -> P:
    """Weight PartitionSpec with a leading (replicated) client axis."""
    dims = [None] * (len(spec.shape) + 1)
    dims[spec.major_axis + 1] = AXIS
    return P(*dims)


def sharded_reconstruct(spec: QSpec, z, ms: int):
    """w = Q z with z sharded P('model'); returns the weight tensor
    with ``spec.shape``, sharded on its major axis. Zero collectives."""
    _check(spec, ms)
    a = spec.major_axis
    loc_moved = (spec.shape[a] // ms,
                 *spec.shape[:a], *spec.shape[a + 1:])

    def local(zl):
        zf = zl.astype(jnp.float32)
        nc = _num_chunks(spec)
        rpc = -(-spec.m_pad_loc // nc)

        def one(c):
            gidx, vals = _chunk_rows(spec, c, rpc)
            return edge_sum(vals * zf[gidx])

        w = jax.lax.map(one, jnp.arange(nc)).reshape(-1)[: spec.m_blk]
        return jnp.moveaxis(w.reshape(loc_moved), 0, a)

    return _shard_map(local, P(AXIS), _out_spec(spec))(
        z.astype(jnp.float32)
    )


def sharded_reconstruct_batched(spec: QSpec, Z, ms: int):
    """W = Q z^(k), K clients at once.  ``Z``: (K, n) with the z axis
    sharded P(None, 'model'); returns (K, *spec.shape) sharded on the
    tensor's major axis.  The chunk indices/values are generated once
    per chunk and contracted against all K local z slabs — zero
    collectives, same as the single-client op."""
    _check(spec, ms)
    a = spec.major_axis
    loc_moved = (spec.shape[a] // ms,
                 *spec.shape[:a], *spec.shape[a + 1:])

    def local(zl):  # (K, n_loc)
        k = zl.shape[0]
        zf = zl.astype(jnp.float32)
        nc = _num_chunks(spec, k)
        rpc = -(-spec.m_pad_loc // nc)

        def one(c):
            gidx, vals = _chunk_rows(spec, c, rpc)
            return jax.lax.map(
                lambda z: edge_sum(vals * z[gidx]), zf
            )  # (K, rpc)

        w = jax.lax.map(one, jnp.arange(nc))  # (nc, K, rpc)
        w = jnp.moveaxis(w, 1, 0).reshape(k, -1)[:, : spec.m_blk]
        return jnp.moveaxis(w.reshape(k, *loc_moved), 1, a + 1)

    return _shard_map(local, P(None, AXIS), _out_spec_b(spec))(
        Z.astype(jnp.float32)
    )


# ---------------------------------------------------------------------------
# Fused shard-local draw: each shard hashes ONLY its own nw_loc windows.
# ---------------------------------------------------------------------------

def _local_draw(spec: QSpec, pl, step, qbits, qpacked=False):
    """This shard's mask bits, drawn from the hash stream at GLOBAL
    coordinates.

    The counter-hash RNG keys every bit on ``(seed, tensor_id, step,
    coord)`` with ``coord`` the global z index, so shard ``sid`` can
    draw its own contiguous slice ``[sid·n_loc, (sid+1)·n_loc)``
    (n_loc = nw_loc·window) without the replicated (n,) mask ever
    existing: the bits equal the global draw's slice EXACTLY.  ``pl``
    is the shard's probability slice — f32, b-bit wire words with
    ``qbits`` (widened-threshold integer compare, as
    ``core.sampling.sample_mask_qhash``), or with ``qpacked`` the
    shard's (n_loc/wpl,) slice of the packed uint32 lane carry
    (``comm.bitpack`` layout — lanes shard cleanly because
    ``wpl | window``), unpacked to shard-local words here.  ``step``
    broadcasts against ``pl``'s leading axes (scalar, or (K,) for the
    batched op).
    """
    from ..comm.bitpack import unpack_words
    from ..core.sampling import bernoulli_u32, mask_u32, quant_threshold_u24

    n_loc = spec.nw_loc * spec.window
    if qpacked:
        pl = unpack_words(pl, n_loc, qbits)
    sid = jax.lax.axis_index(AXIS).astype(jnp.uint32)
    coords = sid * jnp.uint32(n_loc) + jnp.arange(n_loc, dtype=jnp.uint32)
    step = jnp.asarray(step, jnp.uint32)
    u = mask_u32(spec.seed, spec.tensor_id, step[..., None], coords)
    if qbits is not None:
        thr = quant_threshold_u24(pl, qbits)
        return ((u >> jnp.uint32(8)) < thr).astype(jnp.float32)
    return bernoulli_u32(u, pl)


def sharded_sample_reconstruct(spec: QSpec, p, step, ms: int, qbits=None,
                               qpacked=False):
    """Fused w = Q·Bern(p) with the DRAW inside the shard_map body.

    ``p``: (n,) probabilities (or quantized words with ``qbits``; or
    the (n/wpl,) packed lane carry with ``qpacked``),
    sharded/shardable P('model'); ``step``: replicated uint32 draw
    word.  Each shard draws only its own ``nw_loc`` windows from the
    hash stream at global coordinates (``_local_draw``) and contracts
    them locally — no replicated (n,) mask is ever materialized, and
    the result is bit-identical to
    ``sharded_reconstruct(spec, sample_mask_hash(p, ...), ms)``.
    """
    _check(spec, ms)
    if qpacked and spec.window % (32 // qbits) != 0:
        raise ValueError(
            f"packed sharded draw needs window % (32//qbits) == 0; got "
            f"window={spec.window}, qbits={qbits}"
        )
    a = spec.major_axis
    loc_moved = (spec.shape[a] // ms,
                 *spec.shape[:a], *spec.shape[a + 1:])

    def local(pl, st):
        zf = _local_draw(spec, pl, st, qbits, qpacked=qpacked)
        nc = _num_chunks(spec)
        rpc = -(-spec.m_pad_loc // nc)

        def one(c):
            gidx, vals = _chunk_rows(spec, c, rpc)
            return edge_sum(vals * zf[gidx])

        w = jax.lax.map(one, jnp.arange(nc)).reshape(-1)[: spec.m_blk]
        return jnp.moveaxis(w.reshape(loc_moved), 0, a)

    return _shard_map(local, (P(AXIS), P()), _out_spec(spec))(
        p, jnp.asarray(step, jnp.uint32)
    )


def sharded_sample_reconstruct_batched(spec: QSpec, Pr, steps, ms: int,
                                       qbits=None, qpacked=False):
    """Fused batched W = Q·Bern(p^(k)): ``Pr`` (K, n) sharded
    P(None, 'model') — or (K, n/wpl) packed lanes with ``qpacked`` —
    ``steps`` (K,) replicated draw words.  One
    in-body draw of the (K, n_loc) local mask slab (global-coordinate
    hash — bit-identical to the replicated draw's slice), one chunk
    index/value generation shared by all K clients, zero collectives.
    """
    _check(spec, ms)
    if qpacked and spec.window % (32 // qbits) != 0:
        raise ValueError(
            f"packed sharded draw needs window % (32//qbits) == 0; got "
            f"window={spec.window}, qbits={qbits}"
        )
    a = spec.major_axis
    loc_moved = (spec.shape[a] // ms,
                 *spec.shape[:a], *spec.shape[a + 1:])

    def local(pl, st):  # (K, n_loc), (K,)
        k = pl.shape[0]
        zf = _local_draw(spec, pl, st, qbits, qpacked=qpacked)
        nc = _num_chunks(spec, k)
        rpc = -(-spec.m_pad_loc // nc)

        def one(c):
            gidx, vals = _chunk_rows(spec, c, rpc)
            return jax.lax.map(
                lambda z: edge_sum(vals * z[gidx]), zf
            )  # (K, rpc)

        w = jax.lax.map(one, jnp.arange(nc))  # (nc, K, rpc)
        w = jnp.moveaxis(w, 1, 0).reshape(k, -1)[:, : spec.m_blk]
        return jnp.moveaxis(w.reshape(k, *loc_moved), 1, a + 1)

    return _shard_map(local, (P(None, AXIS), P()), _out_spec_b(spec))(
        Pr, jnp.asarray(steps, jnp.uint32)
    )


# ---------------------------------------------------------------------------
# Plan-path transpose: shard-local gather over the cached plan slabs.
# ---------------------------------------------------------------------------

def _plan_num_chunks(spec: QSpec, deg: int) -> int:
    """Window-chunk count bounding the (wpc·window·deg) gather temps."""
    per_win = spec.window * deg * 12  # rows + vals + gathered f32
    return max(1, min(spec.nw_loc,
                      (spec.nw_loc * per_win) // TARGET_CHUNK_BYTES))


def _plan_local(spec: QSpec, rows_l, vals_l, deg: int, g_pad):
    """One shard's grad_z: gather + deg-reduce over its local windows.

    ``rows_l`` (nw_loc, window·deg) block-local source rows, ``vals_l``
    (nw_loc, window, deg), ``g_pad`` (m_pad_loc,).  Window-chunked via
    ``lax.map`` when the gather temporaries exceed TARGET_CHUNK_BYTES.
    """
    nw_loc, rpw = spec.nw_loc, spec.rows_per_window
    nc = _plan_num_chunks(spec, deg)
    if nc == 1:
        return plan_window_apply(spec, rows_l, vals_l, deg, g_pad, nw_loc)
    wpc = -(-nw_loc // nc)
    nc = -(-nw_loc // wpc)
    pad = nc * wpc - nw_loc
    rows_c = jnp.pad(rows_l, ((0, pad), (0, 0))).reshape(nc, wpc, -1)
    vals_c = jnp.pad(vals_l, ((0, pad), (0, 0), (0, 0))).reshape(
        nc, wpc, spec.window, deg
    )
    g_c = jnp.pad(g_pad, (0, pad * rpw)).reshape(nc, wpc * rpw)
    out = jax.lax.map(
        lambda xs: plan_window_apply(spec, xs[0], xs[1], deg, xs[2], wpc),
        (rows_c, vals_c, g_c),
    )
    return out.reshape(-1)[: nw_loc * spec.window]


def _plan_operands(spec: QSpec, order: str):
    """Global plan slabs (jnp) + deg; shard_map slices the window axis."""
    plan = build_transpose_plan(spec, order)
    rows = jnp.asarray(plan.rows.reshape(spec.num_windows, -1))
    return rows, jnp.asarray(plan.vals), plan.deg


def _sharded_grad_z_plan(spec: QSpec, grad_w, order: str):
    rows, vals, deg = _plan_operands(spec, order)

    def local(gl, rows_l, vals_l):
        gm = jnp.moveaxis(gl, spec.major_axis, 0).reshape(-1)
        g_pad = jnp.pad(gm.astype(jnp.float32),
                        (0, spec.m_pad_loc - spec.m_blk))
        return _plan_local(spec, rows_l, vals_l, deg, g_pad)

    return _shard_map(
        local,
        (_out_spec(spec), P(AXIS, None), P(AXIS, None, None)),
        P(AXIS),
    )(grad_w, rows, vals)


def _sharded_grad_z_batched_plan(spec: QSpec, grad_W, order: str):
    rows, vals, deg = _plan_operands(spec, order)

    def local(gl, rows_l, vals_l):  # gl (K, local tensor block)
        k = gl.shape[0]
        gm = jnp.moveaxis(gl, spec.major_axis + 1, 1).reshape(k, -1)
        g_pad = jnp.pad(gm.astype(jnp.float32),
                        ((0, 0), (0, spec.m_pad_loc - spec.m_blk)))
        return jax.lax.map(
            lambda g: _plan_local(spec, rows_l, vals_l, deg, g), g_pad
        )

    return _shard_map(
        local,
        (_out_spec_b(spec), P(AXIS, None), P(AXIS, None, None)),
        P(None, AXIS),
    )(grad_W, rows, vals)


def sharded_grad_z(spec: QSpec, grad_w, ms: int):
    """Q^T g; g has spec.shape (any sharding — in_specs reshards to the
    major axis); returns (n,) f32 sharded P('model'). Zero collectives
    beyond the input reshard (none when g is already major-sharded).

    Dispatches plan (shard-local gather) vs scatter (oracle) via
    ``resolve_bwd_path()``.
    """
    _check(spec, ms)
    kind, order = resolve_bwd_path()
    if kind == "plan":
        return _sharded_grad_z_plan(spec, grad_w, order)

    def local(gl):
        gm = jnp.moveaxis(gl, spec.major_axis, 0).reshape(-1)  # (m_blk,)
        g_pad = jnp.pad(gm.astype(jnp.float32),
                        (0, spec.m_pad_loc - spec.m_blk))
        nc = _num_chunks(spec)
        rpc = -(-spec.m_pad_loc // nc)
        nloc = spec.nw_loc * spec.window

        def step(gz, c):
            gidx, vals = _chunk_rows(spec, c, rpc)
            rows, live = _chunk_live_rows(spec, c, rpc)
            upd = (vals * (g_pad[rows] * live)[:, None]).reshape(-1)
            return gz.at[gidx.reshape(-1)].add(upd), None

        gz, _ = jax.lax.scan(step, jnp.zeros((nloc,), jnp.float32),
                             jnp.arange(nc))
        return gz

    return _shard_map(local, _out_spec(spec), P(AXIS))(grad_w)


def sharded_grad_z_batched(spec: QSpec, grad_W, ms: int):
    """Q^T g per client; ``grad_W``: (K, *spec.shape); returns (K, n)
    f32 sharded P(None, 'model').  One generation of the chunk
    indices/values (scatter) or one shared plan slab (plan, default)
    feeds all K clients; dispatch via ``resolve_bwd_path()``."""
    _check(spec, ms)
    kind, order = resolve_bwd_path()
    if kind == "plan":
        return _sharded_grad_z_batched_plan(spec, grad_W, order)

    def local(gl):  # (K, local tensor block)
        k = gl.shape[0]
        gm = jnp.moveaxis(gl, spec.major_axis + 1, 1).reshape(k, -1)
        g_pad = jnp.pad(gm.astype(jnp.float32),
                        ((0, 0), (0, spec.m_pad_loc - spec.m_blk)))
        nc = _num_chunks(spec, k)
        rpc = -(-spec.m_pad_loc // nc)
        nloc = spec.nw_loc * spec.window

        def step(gz, c):
            gidx, vals = _chunk_rows(spec, c, rpc)
            rows, live = _chunk_live_rows(spec, c, rpc)
            flat = gidx.reshape(-1)

            def one(args):
                gz_k, g_k = args
                upd = (vals * (g_k[rows] * live)[:, None]).reshape(-1)
                return gz_k.at[flat].add(upd)

            return jax.lax.map(one, (gz, g_pad)), None

        gz, _ = jax.lax.scan(step, jnp.zeros((k, nloc), jnp.float32),
                             jnp.arange(nc))
        return gz

    return _shard_map(local, _out_spec_b(spec), P(None, AXIS))(grad_W)
