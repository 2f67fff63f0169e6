"""Per-spec caches for Q's generation plans: rows, and the transpose.

Two spec-static artifacts are built here ONCE per ``QSpec`` (numpy, at
first use) and reused by every trace that touches the spec:

``row_plan(spec)`` — the forward row plan ``(gidx (m_pad, d) global
z-indices, vals (m_pad, d) f32)``.  ``core.reconstruct`` previously
recomputed this (hash + Box–Muller over all m_pad rows) inside every
traced call, so a fwd+bwd pair in one jit generated Q twice and every
retrace paid it again; cached as numpy it becomes a trace-time
constant shared by forward and backward.

``build_transpose_plan(spec)`` — the TENTPOLE of the gather backward:
the inversion of the row plan into per-coordinate incoming-edge lists.
Every nonzero of padded row ``rp`` lands in window ``w = rp //
rows_per_window`` (rows tile windows contiguously in the padded row
space, across shard blocks too), so Q^T factors into ``num_windows``
independent ``(window, rows_per_window)`` blocks.  A one-time counting
sort over the ``m_pad·d`` edges produces, for every z coordinate, the
degree-padded list of (window-local source row, coefficient) pairs:

    rows (num_windows, window, deg) int32   in [0, rows_per_window)
    vals (num_windows, window, deg) f32     0.0 on padding entries

with ``deg = max_in_degree`` over all coordinates (exact, computed by
the counting sort; expected value ``rows_per_window·d/window =
compression·d``).  Padding entries point at row 0 with value 0, so a
consumer may gather them unconditionally.  Edges of rows beyond the
valid range (``padded_row_valid`` false) are EXCLUDED at build time —
they carry hash-generated values but always multiply a zero cotangent.

The backward then becomes a batch-friendly gather + reduction,

    grad_z[w·window + c] = sum_e vals[w, c, e] · g_pad[w·rpw + rows[w, c, e]]

instead of a scatter-add of m_pad·d updates (see
``core.reconstruct.grad_z_plan_ref``).

Ordering contract: floating-point addition is not associative, so the
EDGE ORDER inside each coordinate's list is part of the numerics.

 - ``order='canonical'`` (default): edges sorted by (source row, slot).
   Deterministic and layout-independent — the same spec always sums in
   the same order, giving bit-reproducible runs across plan consumers
   that reduce the deg axis sequentially.
 - ``order='slot'``: edges sorted by (slot k, source row) — a second
   deterministic ordering used to test the cross-order ``allclose``
   contract.

Exact equality holds per ordering mode (same plan -> same bits);
across modes, and against the scatter oracle, the contract is
``allclose`` (see tests/test_transpose_plan.py).

``build_block_plan(spec, bm)`` re-bins the same edges by the Pallas
backward's row-block grid (``kernels.qz_reconstruct``): cell (window
i, block j, sub-block b, coordinate c) holds the edges whose source
row falls in rows [j·bm + b·sub, j·bm + (b+1)·sub) of window i, rows
stored sub-block-relative so the kernel's gather is an in-sub-block
one-hot contraction.  The ``bm``-row block fixes the summation order
(the kernel adds a block's sub-blocks into one accumulator, sub-block
0 first); ``sub`` (``sub_block_rows``) only sizes each one-hot.  In
canonical order a block's edges, sub-block by sub-block, are exactly
its ascending-row sequence; in slot order they are that sequence
grouped by sub-block.

Path gating: ``resolve_bwd_path()`` decides scatter vs plan at TRACE
time.  The ``REPRO_BWD_PLAN`` env var overrides the process default
(``set_default_bwd_path``), mirroring ``REPRO_RECONSTRUCT_IMPL`` — an
already-compiled shape keeps its path.  The scatter path is kept as
the bit-exactness oracle.
"""

from __future__ import annotations

import contextlib
import functools
import os
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from .qspec import QSpec, padded_row_valid, padded_row_window, row_indices, row_values

# ---------------------------------------------------------------------------
# Backward-path gate (trace-time, env-overridable)
# ---------------------------------------------------------------------------

_ORDERS = ("canonical", "slot")
# accepted spellings of the gate; "plan" is canonical-order
_VALID_BWD_PATHS = ("plan", "plan:canonical", "plan:slot", "scatter")
_DEFAULT_BWD_PATH = "plan"


def set_default_bwd_path(path: str) -> None:
    """Set the process-wide default transpose path (plan | scatter)."""
    global _DEFAULT_BWD_PATH
    if path not in _VALID_BWD_PATHS:
        raise ValueError(
            f"unknown bwd path {path!r}; valid paths: "
            f"{', '.join(_VALID_BWD_PATHS)}"
        )
    _DEFAULT_BWD_PATH = path


def default_bwd_path() -> str:
    """Effective transpose path: ``REPRO_BWD_PLAN`` env overrides the
    ``set_default_bwd_path`` process default — read at trace time, so
    flipping it between jit calls of different closures needs no code
    edit (an already-compiled function keeps its path)."""
    env = os.environ.get("REPRO_BWD_PLAN")
    if env is None:
        return _DEFAULT_BWD_PATH
    if env not in _VALID_BWD_PATHS:
        raise ValueError(
            f"REPRO_BWD_PLAN={env!r} is not a valid bwd path; valid: "
            f"{', '.join(_VALID_BWD_PATHS)}"
        )
    return env


def resolve_bwd_path(path: str | None = None):
    """``(kind, order)`` for a path string (default: the gated one).

    kind is 'plan' or 'scatter'; order is the plan edge ordering
    ('canonical' | 'slot', None for scatter).
    """
    path = path or default_bwd_path()
    if path not in _VALID_BWD_PATHS:
        raise ValueError(
            f"unknown bwd path {path!r}; valid paths: "
            f"{', '.join(_VALID_BWD_PATHS)}"
        )
    if path == "scatter":
        return "scatter", None
    _, _, order = path.partition(":")
    return "plan", order or "canonical"


# ---------------------------------------------------------------------------
# Cached forward row plan (spec-static)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _host_eval():
    """Concrete, eager evaluation of a host-side plan build.  The first
    build may happen inside a trace (jit/vmap/grad of a consumer, or a
    shard_map body, whose manual context mesh an eager op must not
    inherit)."""
    with jax.ensure_compile_time_eval(), jax.sharding.use_abstract_mesh(
            jax.sharding.AbstractMesh((), ())):
        yield


# Bounded like ops._vmap_cores: eviction costs a one-time rebuild,
# never correctness.  Entries are O(m_pad·d) numpy, so keep it small.
@functools.lru_cache(maxsize=32)
def row_plan(spec: QSpec):
    """Hash-RNG indices/values for ALL padded rows, built once (numpy).

    Returns ``(gidx (m_pad, d) int32 global z-indices, vals (m_pad, d)
    f32)`` — byte-identical to the traced generation (same jnp hash
    ops, evaluated eagerly and frozen).
    """
    rp = np.arange(spec.m_pad, dtype=np.uint32)
    with _host_eval():
        win = np.asarray(padded_row_window(spec, rp.astype(np.int32)))
        idx = np.asarray(row_indices(spec, rp))
        vals = np.asarray(row_values(spec, rp, dtype=jnp.float32))
    gidx = win[:, None].astype(np.int64) * spec.window + idx
    return gidx.astype(np.int32), vals


# ---------------------------------------------------------------------------
# Transpose plans
# ---------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class TransposePlan:
    """Inverted row plan: per-coordinate padded incoming-edge lists.

    ``rows[w, c, e]`` is the window-local source row (in
    [0, rows_per_window)) of edge ``e`` into coordinate ``w·window+c``;
    ``vals[w, c, e]`` its Q coefficient (0.0 on padding entries, which
    point at row 0).  ``counts`` is the exact per-coordinate in-degree
    (n,), ``deg`` its max (>= 1).
    """

    order: str
    deg: int
    rows: np.ndarray  # (num_windows, window, deg) int32
    vals: np.ndarray  # (num_windows, window, deg) f32
    counts: np.ndarray  # (n,) int32

    @property
    def n_edges(self) -> int:
        return int(self.counts.sum())


@dataclass(frozen=True, eq=False)
class BlockPlan:
    """Transpose plan re-binned to the Pallas (window, row-block) grid.

    Each ``bm``-row block j of window i splits into ``nsub`` sub-blocks
    of ``sub`` rows.  ``rows[i, j, b, e, c]`` is SUB-BLOCK-relative (in
    [0, sub)): the source row of edge ``e`` into in-window coordinate
    ``c``, among the rows [j·bm + b·sub, j·bm + (b+1)·sub) of window i.
    Slot-major, so one grid step reads its ``nsub`` (deg, window)
    sub-plans with the coordinates on the lanes.  ``deg`` is the max
    in-degree over all (window, block, sub-block, coordinate) cells;
    padding entries point at row 0 with value 0.
    """

    order: str
    bm: int
    bpw: int
    sub: int
    deg: int
    rows: np.ndarray  # (num_windows, bpw, nsub, deg, window) int32
    vals: np.ndarray  # (num_windows, bpw, nsub, deg, window) f32

    @property
    def nsub(self) -> int:
        return self.bm // self.sub

    @property
    def onehot_elems(self) -> int:
        """One-hot elements a grid block builds: ``nsub·deg·sub·window``
        (per client row of the gather, the VPU work the plan costs)."""
        return self.nsub * self.deg * self.sub * self.rows.shape[-1]


def _edges(spec: QSpec, order: str):
    """Flat valid-edge arrays (key basis, src row local, vals) in the
    requested enumeration order; counting-sort key is added by callers."""
    if order not in _ORDERS:
        raise ValueError(f"unknown plan order {order!r}; valid: {_ORDERS}")
    gidx, vals = row_plan(spec)
    rp = np.arange(spec.m_pad, dtype=np.int64)
    with _host_eval():
        valid = np.asarray(padded_row_valid(spec, rp))
    r_local = (rp % spec.rows_per_window).astype(np.int64)
    coord = gidx.astype(np.int64)  # (m_pad, d) global z coordinate
    rows2 = np.broadcast_to(r_local[:, None], coord.shape)
    mask2 = np.broadcast_to(valid[:, None], coord.shape)
    if order == "canonical":  # row-major: per coord sorted by (row, k)
        c, r, v, mk = (coord.reshape(-1), rows2.reshape(-1),
                       vals.reshape(-1), mask2.reshape(-1))
    else:  # 'slot': k-major enumeration -> per coord sorted by (k, row)
        c, r, v, mk = (coord.T.reshape(-1), rows2.T.reshape(-1),
                       vals.T.reshape(-1), mask2.T.reshape(-1))
    return c[mk], r[mk], v[mk]


def _pack(keys, rows, vals, num_cells: int):
    """Counting-sort edges by cell key into degree-padded (num_cells,
    deg) slabs.  Returns (rows_pad, vals_pad, counts, deg)."""
    perm = np.argsort(keys, kind="stable")  # stable: keeps edge order
    ks, rs, vs = keys[perm], rows[perm], vals[perm]
    counts = np.bincount(ks, minlength=num_cells).astype(np.int64)
    deg = int(max(1, counts.max() if counts.size else 1))
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    pos = np.arange(ks.size, dtype=np.int64) - starts[ks]
    rows_pad = np.zeros((num_cells, deg), np.int32)
    vals_pad = np.zeros((num_cells, deg), np.float32)
    rows_pad[ks, pos] = rs
    vals_pad[ks, pos] = vs
    return rows_pad, vals_pad, counts.astype(np.int32), deg


def plan_window_apply(spec: QSpec, rows, vals, deg: int, g, nwin: int):
    """The ONE window-blocked plan-apply expression: gather + deg-sum.

    ``rows`` (nwin, window·deg) window-LOCAL source rows, ``vals``
    (nwin, window, deg), ``g`` (nwin·rows_per_window,) the cotangent
    slice those windows own; returns (nwin·window,) grad-z.

    Every window-blocked consumer (the chunked backward in
    ``kernels.ops``, the shard-local backward in
    ``kernels.qz_sharded``) MUST route through this helper: the
    deg-axis summation order is the ordering contract, and a drifting
    copy would silently break the cross-path bit-reproducibility the
    tests pin.  (The global ref path uses a flat gather over global
    row ids instead — ``core.reconstruct._plan_apply`` — which is a
    genuinely different, also-pinned form.)
    """
    g_win = g.reshape(nwin, spec.rows_per_window)
    gath = jnp.take_along_axis(
        g_win, rows, axis=1,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )
    return (vals * gath.reshape(nwin, spec.window, deg)).sum(-1).reshape(-1)


@functools.lru_cache(maxsize=32)
def build_transpose_plan(spec: QSpec,
                         order: str = "canonical") -> TransposePlan:
    """Invert the row plan into per-coordinate incoming-edge lists."""
    c, r, v = _edges(spec, order)
    rows_pad, vals_pad, counts, deg = _pack(c, r, v, spec.n)
    nw = spec.num_windows
    return TransposePlan(
        order=order, deg=deg,
        rows=rows_pad.reshape(nw, spec.window, deg),
        vals=vals_pad.reshape(nw, spec.window, deg),
        counts=counts,
    )


# Rows of one sub-block: the one-hot each plan slot of the backward's
# gather builds is (SUB_ROWS, window).  Its time follows the one-hot's
# size: of 128 and 64 rows, 64 ran faster on a TPU v5e (PERF.md).
SUB_ROWS = 64


def sub_block_rows(spec: QSpec, bm: int) -> int:
    """Rows of one sub-block of the backward's ``bm``-row block.

    ``SUB_ROWS`` where the block's rows tile by it and the window fills
    more than one sub-block (each one-hot is then a fraction of the
    block's, with a far shallower plan); else the whole block (``bm``
    of at most ``SUB_ROWS`` rows, or a window of at most ``SUB_ROWS``
    rows, whose other sub-blocks would hold no edge).
    """
    if bm > SUB_ROWS and bm % SUB_ROWS == 0 \
            and spec.rows_per_window > SUB_ROWS:
        return SUB_ROWS
    return bm


def build_block_plan(spec: QSpec, bm: int,
                     order: str = "canonical") -> BlockPlan:
    """Transpose plan binned per (window, bm-row block, sub-block,
    coordinate), sub-blocks of ``sub_block_rows(spec, bm)`` rows."""
    return _bin_block_plan(spec, bm, sub_block_rows(spec, bm), order)


@functools.lru_cache(maxsize=32)
def _bin_block_plan(spec: QSpec, bm: int, sub: int,
                   order: str = "canonical") -> BlockPlan:
    """``build_block_plan`` with sub-blocks of ``sub`` rows (``sub``
    divides ``bm``; ``sub = bm`` is one sub-block per block)."""
    if bm % sub:
        raise ValueError(f"sub-block of {sub} rows does not divide bm={bm}")
    c, r, v = _edges(spec, order)
    nw, win = spec.num_windows, spec.window
    bpw, nsub = max(1, -(-spec.rows_per_window // bm)), bm // sub
    # sub-block index within the window (block j, sub-block b -> j·nsub + b)
    key = ((c // win) * (bpw * nsub) + r // sub) * win + c % win
    rows_pad, vals_pad, _, deg = _pack(
        key.astype(np.int64), (r % sub).astype(np.int64), v,
        nw * bpw * nsub * win)
    shape = (nw, bpw, nsub, win, deg)
    return BlockPlan(
        order=order, bm=bm, bpw=bpw, sub=sub, deg=deg,
        rows=np.ascontiguousarray(np.swapaxes(rows_pad.reshape(shape), 3, 4)),
        vals=np.ascontiguousarray(np.swapaxes(vals_pad.reshape(shape), 3, 4)),
    )
