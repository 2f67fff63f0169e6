"""Reference (pure-jnp) reconstruction ``w = Q z`` and its transpose.

This is the oracle the Pallas kernel and the distributed shard_map op
are validated against, and the default path on CPU.  Differentiable in
``z`` (``grad_z = Q^T grad_w``, exactly the paper's ``∇_s L =
(∇_w L ⊙ Q)`` chain).

Layout (QSpec docstring): rows live in a padded per-block space of
``shard_count`` x ``m_pad_loc``; valid rows map to the tensor flattened
with ``major_axis`` moved to the front (sharding-major order).  All
functions here compute globally — the distributed equivalent is
``kernels.qz_sharded``.

Row plan caching: Q's hash-RNG indices/values are spec-static, so
``_row_plan`` routes through the per-spec numpy cache
(``core.transpose_plan.row_plan``) and enters every trace as a
CONSTANT — a fwd+bwd pair in one jit shares one generation, and no
trace ever re-pays the hash + Box–Muller sweep over m_pad rows.  (The
chunked and sharded FORWARD paths still regenerate per chunk by
design: they exist to bound temporaries, which a baked O(m_pad·d)
constant would defeat; the scatter oracle also keeps traced
generation — XLA:CPU pessimizes scatters whose index operand is a
large constant.  The plan BACKWARD is different: its O(n·deg) slab is
static read-only data resident once per (spec, order) — chunking
bounds the gather TEMPORARIES, not the slab; callers needing the
scatter path's strict O(rpc·d) footprint set
``REPRO_BWD_PLAN=scatter``.)  All
constant-index gathers go through raw PROMISE_IN_BOUNDS ``lax.gather``
(``_gather_rows``): ``jnp.take``'s bounds masks and negative-index
normalization would be constant-folded over the O(m_pad·d) slab for
tens of seconds per trace at bench scale.

The transpose ``grad_z = Q^T grad_w`` has two implementations, gated
at trace time by ``core.transpose_plan.resolve_bwd_path()`` (env
``REPRO_BWD_PLAN``; default 'plan'):

 - PLAN (default): a gather + reduction over each coordinate's
   incoming edges.  Every nonzero of window ``i``'s rows lands in
   window ``i``'s coordinates, so Q^T factors into ``num_windows``
   independent (window × rows_per_window·d) blocks; the cached
   ``TransposePlan`` inverts the row plan once (counting sort, numpy)
   into degree-padded per-coordinate edge lists ``(src_row, val)`` and
   the backward becomes

       grad_z[w, c] = sum_e vals[w, c, e] · g_pad[w·rpw + rows[w, c, e]]

   — a contiguous ``take_along_axis`` + multiply + deg-axis sum that
   vectorizes (and batches over K clients) where the scatter
   serializes.  Ordering contract: the deg-axis sum runs in the plan's
   edge order, so runs are bit-reproducible per ordering mode
   ('canonical' = sorted by source row; 'slot' for cross-order tests)
   and ``allclose`` across modes and vs the scatter oracle.
 - SCATTER (oracle): the original ``.at[gidx].add`` scatter-add,
   kept as the bit-exactness baseline (``grad_z_scatter_ref``).

Batched (multi-client) variants: ``reconstruct_batched_ref`` /
``grad_z_batched_ref`` take a stacked ``Z (K, n)`` and use the cached
plan ONCE, contracting it against all K client vectors.
``jax.vmap(reconstruct_ref)`` shares the constant too, but the batched
entry also picks a size-dependent contraction strategy
(``_BATCH_MAP_THRESHOLD``):

 - LARGE specs (``m_pad·d`` above the threshold): a ``lax.map`` over
   clients of 1-D gathers.  XLA:CPU lowers the (K, m_pad, d)
   mega-gather to a strided column gather that is slower than K
   contiguous row gathers, and the map keeps temporaries at
   O(m_pad·d) instead of O(K·m_pad·d).
 - SMALL specs: one fused batched gather + einsum, exactly what vmap
   would emit.  Inside ``vmap(grad(lax.scan))`` (the federated round)
   a ``lax.map`` body costs ~ms per iteration in XLA:CPU while-loop
   form, which at test scale swamps any savings.

The crossover point is tuned for XLA:CPU (re-measured with the plan
backward by ``benchmarks.run bench_threshold`` — see the committed
``batch_map_threshold`` rows in BENCH_reconstruct.json); set the env
var ``REPRO_BATCH_MAP_THRESHOLD`` (elements of hash work
``m_pad * d``) to retune on other backends without code edits — it is
read at trace time, so changing it between jit calls of different
shapes takes effect immediately (an already-compiled shape keeps its
strategy).
"""

from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from ..tracing import QZ_LAYOUT
from .qspec import (
    QSpec,
    edge_sum,
    padded_row_valid,
    padded_row_window,
    row_indices,
    row_values,
)
from .transpose_plan import build_transpose_plan, resolve_bwd_path, row_plan


def _row_plan(spec: QSpec):
    """Cached hash-RNG indices/values for ALL padded rows (constants).

    Returns (gidx (m_pad, d) global z-indices, vals (m_pad, d) f32) —
    numpy from the per-spec cache, so they enter the trace as
    constants and fwd+bwd in one jit share one generation.
    """
    gidx, vals = row_plan(spec)
    return jnp.asarray(gidx), jnp.asarray(vals)


def _row_plan_traced(spec: QSpec):
    """Hash-RNG indices/values generated IN-GRAPH (traced ops).

    The scatter oracle keeps this: XLA:CPU pessimizes scatters whose
    index operand is a large constant (measured 5-10x slower than the
    same scatter with computed indices), so baking the cached plan into
    the scatter path would corrupt the very baseline the plan path is
    measured against.
    """
    rp = jnp.arange(spec.m_pad, dtype=jnp.uint32)
    win = padded_row_window(spec, rp.astype(jnp.int32))
    idx = row_indices(spec, rp)  # (m_pad, d) in-window
    vals = row_values(spec, rp, dtype=jnp.float32)
    return win[:, None] * spec.window + idx, vals


def _gather_rows(x, idx2d):
    """1-D gather ``x[idx2d[:, 0]]`` with no index arithmetic in-graph.

    ``jnp.take``/``take_along_axis`` emit bounds masks and negative-
    index normalization; over the O(m_pad·d) CONSTANT index slabs of
    the cached plans XLA constant-folds those elementwise ops for tens
    of seconds per trace at bench scale.  Indices here are in-bounds by
    construction, so a raw ``lax.gather`` with PROMISE_IN_BOUNDS skips
    all of it.
    """
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(0,), start_index_map=(0,)
    )
    return jax.lax.gather(
        x, idx2d, dn, slice_sizes=(1,),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _gather_cols(x2d, idx2d):
    """Batched column gather ``x2d[:, idx2d[:, 0]]`` -> (K, N), same
    PROMISE_IN_BOUNDS / no-index-arithmetic rationale as
    ``_gather_rows`` (one shared constant index slab, K rows ride
    along in the slice)."""
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(0,), collapsed_slice_dims=(1,), start_index_map=(1,)
    )
    return jax.lax.gather(
        x2d, idx2d, dn, slice_sizes=(x2d.shape[0], 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _w_padded(spec: QSpec, z):
    """All padded rows: w_pad (m_pad,) f32."""
    gidx, vals = _row_plan(spec)
    zg = _gather_rows(z.astype(jnp.float32), gidx.reshape(-1, 1))
    return edge_sum(vals * zg.reshape(spec.m_pad, spec.d))


def _select_valid(spec: QSpec, w_pad):
    """(m_pad,) -> (m,) in moved (sharding-major) flat order."""
    return w_pad.reshape(spec.shard_count, spec.m_pad_loc)[
        :, : spec.m_blk
    ].reshape(-1)


def _insert_padding(spec: QSpec, flat_moved):
    """(m,) moved order -> (m_pad,) with per-block padding zeros."""
    blocks = flat_moved.reshape(spec.shard_count, spec.m_blk)
    return jnp.pad(
        blocks, ((0, 0), (0, spec.m_pad_loc - spec.m_blk))
    ).reshape(-1)


@jax.named_scope(QZ_LAYOUT)
def _unmove(spec: QSpec, flat_moved):
    w = flat_moved.reshape(spec.moved_shape)
    return jnp.moveaxis(w, 0, spec.major_axis)


@jax.named_scope(QZ_LAYOUT)
def _move(spec: QSpec, w):
    return jnp.moveaxis(w, spec.major_axis, 0).reshape(-1)


def _select_valid_batched(spec: QSpec, w_pad):
    """(K, m_pad) -> (K, m) in moved (sharding-major) flat order."""
    k = w_pad.shape[0]
    return w_pad.reshape(k, spec.shard_count, spec.m_pad_loc)[
        :, :, : spec.m_blk
    ].reshape(k, spec.m)


def _insert_padding_batched(spec: QSpec, flat_moved):
    """(K, m) moved order -> (K, m_pad) with per-block padding zeros."""
    k = flat_moved.shape[0]
    blocks = flat_moved.reshape(k, spec.shard_count, spec.m_blk)
    return jnp.pad(
        blocks, ((0, 0), (0, 0), (0, spec.m_pad_loc - spec.m_blk))
    ).reshape(k, spec.m_pad)


@jax.named_scope(QZ_LAYOUT)
def _unmove_batched(spec: QSpec, flat_moved):
    """(K, m) moved flat order -> (K, *spec.shape)."""
    k = flat_moved.shape[0]
    w = flat_moved.reshape(k, *spec.moved_shape)
    return jnp.moveaxis(w, 1, spec.major_axis + 1)


@jax.named_scope(QZ_LAYOUT)
def _move_batched(spec: QSpec, w):
    """(K, *spec.shape) -> (K, m) moved flat order."""
    return jnp.moveaxis(w, spec.major_axis + 1, 1).reshape(w.shape[0], -1)


# Above this much hash work (m_pad * d elements) the once-per-round
# regeneration saving beats XLA:CPU's per-iteration lax.map overhead.
# Default for XLA:CPU; override via REPRO_BATCH_MAP_THRESHOLD (see
# module docstring) when retuning for TPU/GPU.
_BATCH_MAP_THRESHOLD = 2_000_000


def _batch_map_threshold() -> int:
    """Effective crossover, env-overridable (read at trace time)."""
    return int(os.environ.get("REPRO_BATCH_MAP_THRESHOLD",
                              _BATCH_MAP_THRESHOLD))


def reconstruct_batched_ref(spec: QSpec, Z, dtype=None, row_sharding=None):
    """W = Q z^(k) for K stacked clients. ``Z``: (K, n) -> (K, *shape)."""
    del row_sharding
    if Z.ndim != 2 or Z.shape[-1] != spec.n:
        raise ValueError(f"Z has shape {Z.shape}, spec expects (K, {spec.n})")
    dtype = dtype or Z.dtype
    gidx, vals = _row_plan(spec)
    zf = Z.astype(jnp.float32)
    if spec.m_pad * spec.d >= _batch_map_threshold():
        flat = gidx.reshape(-1, 1)
        w_pad = jax.lax.map(
            lambda z: edge_sum(
                vals * _gather_rows(z, flat).reshape(spec.m_pad, spec.d)),
            zf,
        )
    else:
        zg = _gather_cols(zf, gidx.reshape(-1, 1)).reshape(
            Z.shape[0], spec.m_pad, spec.d
        )
        w_pad = edge_sum(vals * zg)
    w = _select_valid_batched(spec, w_pad)
    return _unmove_batched(spec, w).astype(dtype)


# ---------------------------------------------------------------------------
# The transpose Q^T g: plan (gather) path and scatter oracle.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _plan_tables_np(spec: QSpec, order: str):
    """Plan slabs for the global gather: rows flattened to GLOBAL
    padded-row ids (n·deg, 1) (windows tile the padded row space
    contiguously: global row = w·rpw + local row), vals (nw, window,
    deg)."""
    plan = build_transpose_plan(spec, order)
    off = np.arange(spec.num_windows, dtype=np.int64)[:, None, None]
    rows = (plan.rows.astype(np.int64)
            + off * spec.rows_per_window).reshape(-1, 1)
    return rows.astype(np.int32), plan.vals, plan.deg


def _plan_tables(spec: QSpec, order: str):
    rows, vals, deg = _plan_tables_np(spec, order)
    return jnp.asarray(rows), jnp.asarray(vals), deg


def _plan_apply(spec: QSpec, rows, vals, deg: int, g_pad):
    """grad_z for one client: one flat gather + deg-axis reduction.

    ``g_pad`` (m_pad,) in padded row space; ``rows`` (n·deg, 1) global
    padded-row ids (``_plan_tables``).  The raw PROMISE_IN_BOUNDS
    gather keeps the constant index slab free of in-graph index
    arithmetic (see ``_gather_rows``).
    """
    gath = _gather_rows(g_pad, rows)
    prod = vals * gath.reshape(spec.num_windows, spec.window, deg)
    return prod.sum(axis=-1).reshape(spec.n)


def grad_z_plan_ref(spec: QSpec, grad_w, order: str = "canonical"):
    """Q^T grad_w as a GATHER over the cached transpose plan."""
    g = _insert_padding(spec, _move(spec, grad_w.astype(jnp.float32)))
    rows, vals, deg = _plan_tables(spec, order)
    return _plan_apply(spec, rows, vals, deg, g)


def grad_z_plan_batched_ref(spec: QSpec, grad_W,
                            order: str = "canonical"):
    """Per-client Q^T grad_w over the plan: (K, *shape) -> (K, n).

    One plan constant feeds all K clients.  Strategy mirrors the
    forward (``_batch_map_threshold``): large specs run a ``lax.map``
    over clients (temporaries O(n·deg), not O(K·n·deg)); small specs
    do one broadcast take_along_axis — identical elementwise expression
    either way, so the deg-axis summation order (the ordering
    contract) is strategy-independent.
    """
    g_pad = _insert_padding_batched(
        spec, _move_batched(spec, grad_W.astype(jnp.float32))
    )
    rows, vals, deg = _plan_tables(spec, order)
    if spec.m_pad * spec.d >= _batch_map_threshold():
        return jax.lax.map(
            lambda g: _plan_apply(spec, rows, vals, deg, g), g_pad
        )
    k = g_pad.shape[0]
    gath = _gather_cols(g_pad, rows)
    prod = vals[None] * gath.reshape(k, spec.num_windows, spec.window, deg)
    return prod.sum(axis=-1).reshape(k, spec.n)


def grad_z_scatter_ref(spec: QSpec, grad_w):
    """Q^T grad_w as the original scatter-add — the bit-exactness
    oracle for the plan path (traced index generation; see
    ``_row_plan_traced``)."""
    g = _insert_padding(spec, _move(spec, grad_w.astype(jnp.float32)))
    gidx, vals = _row_plan_traced(spec)
    out = jnp.zeros((spec.n,), jnp.float32)
    return out.at[gidx.reshape(-1)].add((vals * g[:, None]).reshape(-1))


def grad_z_scatter_batched_ref(spec: QSpec, grad_W):
    """Per-client scatter-add transpose (oracle for the batched plan)."""
    g_pad = _insert_padding_batched(
        spec, _move_batched(spec, grad_W.astype(jnp.float32))
    )
    gidx, vals = _row_plan_traced(spec)
    gidx = gidx.reshape(-1)
    if spec.m_pad * spec.d >= _batch_map_threshold():
        # the scatter-add batches WELL under vmap on XLA:CPU (lax.map
        # of scatters measured 2x slower, the (K, m_pad*d) one-shot
        # batched scatter 1.5x slower)
        def one(gk):
            out = jnp.zeros((spec.n,), jnp.float32)
            return out.at[gidx].add((vals * gk[:, None]).reshape(-1))

        return jax.vmap(one)(g_pad)
    contrib = (vals[None] * g_pad[:, :, None]).reshape(g_pad.shape[0], -1)
    out = jnp.zeros((g_pad.shape[0], spec.n), jnp.float32)
    return out.at[:, gidx].add(contrib)


def grad_z_batched_ref(spec: QSpec, grad_W, row_sharding=None):
    """Q^T grad_w per client: (K, *shape) -> (K, n) f32.

    Dispatches plan vs scatter via ``resolve_bwd_path()`` (env
    ``REPRO_BWD_PLAN``, read at trace time).
    """
    del row_sharding
    kind, order = resolve_bwd_path()
    if kind == "plan":
        return grad_z_plan_batched_ref(spec, grad_W, order)
    return grad_z_scatter_batched_ref(spec, grad_W)


def reconstruct_ref(spec: QSpec, z, dtype=None, row_sharding=None):
    """w = Q z for one tensor. ``z``: (n,) -> weights with spec.shape."""
    del row_sharding  # the ref path computes globally
    if z.shape != (spec.n,):
        raise ValueError(f"z has shape {z.shape}, spec expects ({spec.n},)")
    dtype = dtype or z.dtype
    w = _select_valid(spec, _w_padded(spec, z))
    return _unmove(spec, w).astype(dtype)


def grad_z_ref(spec: QSpec, grad_w, row_sharding=None):
    """Q^T grad_w — the reconstruction transpose. Returns (n,) f32.

    Dispatches plan vs scatter via ``resolve_bwd_path()`` (env
    ``REPRO_BWD_PLAN``, read at trace time).
    """
    del row_sharding
    kind, order = resolve_bwd_path()
    if kind == "plan":
        return grad_z_plan_ref(spec, grad_w, order)
    return grad_z_scatter_ref(spec, grad_w)


def materialize_q(spec: QSpec):
    """Dense (m, n) Q in NATURAL (spec.shape row-major) order —
    tests/small-scale theory checks ONLY."""
    gidx, vals = _row_plan(spec)
    q_pad = jnp.zeros((spec.m_pad, spec.n), jnp.float32)
    q_pad = q_pad.at[jnp.arange(spec.m_pad)[:, None], gidx].add(vals)
    q_moved = q_pad.reshape(spec.shard_count, spec.m_pad_loc, spec.n)[
        :, : spec.m_blk
    ].reshape(spec.m, spec.n)
    # moved flat order -> natural order rows
    q = q_moved.reshape(*spec.moved_shape, spec.n)
    q = jnp.moveaxis(q, 0, spec.major_axis)
    return q.reshape(spec.m, spec.n)
