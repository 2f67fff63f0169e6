"""Pallas TPU kernels: materialization-free ``w = Q z`` reconstruction.

Design (DESIGN.md §3):

 - grid = (num_windows, blocks_per_window); block (i, j) produces ``bm``
   weights whose Q-rows all read from z-window ``i`` — the (window,)
   slice of ``z`` is the only HBM->VMEM traffic besides the output tile.
 - indices/values are *regenerated* inside the kernel from the hash RNG
   (no Q operand at all), so HBM traffic is O(n + m) instead of
   O(m·d) for a materialized sparse Q.
 - every kernel is K-client batched: the client axis rides on the
   sublanes of each block (``(K, window)`` z-slab in, ``(K, bm)``
   weight tile out, rows on the lanes), so Q's hash-RNG edges are
   regenerated once per block instead of K times.  The single-client
   entry points are the K=1 case of the same kernels.
 - the in-window gather ``z[idx]`` is one MXU contraction per edge
   slot: for each of the ``d`` (static) edge slots, the (window, bm)
   one-hot of that slot's column against the lane-aligned window,
   ``zslab (K, window) @ onehot (window, bm)``, scaled by the slot's
   values and accumulated in ascending slot order.  Every array stays
   2-D and lane-aligned: no reshape ever crosses lanes.

Backward ``grad_z = Q^T grad_w`` (``kernels.ops`` dispatches via
``core.transpose_plan.resolve_bwd_path()``):

 - PLAN (default, ``qz_reconstruct_batched_bwd_plan``): the cached
   per-spec transpose plan re-binned to this grid (``build_block_plan``)
   and, inside each ``bm``-row block, to sub-blocks of ``sub`` rows
   (``sub_block_rows``: ``SUB_ROWS`` = 64 where the block's rows tile
   by it, else ``bm``) — cell (window i, block j, sub-block b,
   coordinate c) carries the degree-padded incoming edges whose source
   row lies in rows [j·bm + b·sub, j·bm + (b+1)·sub) of window i, rows
   stored sub-block-relative.  For each sub-block, then each of its
   ``deg`` plan slots, the gather is ``g[:, b·sub:(b+1)·sub] (K, sub)
   @ onehot (sub, window)``, scaled by the slot's values, all into one
   accumulator that the block adds to its window's grad-z once.  Each
   one-hot is ``sub`` rows high with a plan far shallower than the
   block's, and the sums are unchanged: in canonical order each
   coordinate adds its edges in ascending source row within a
   ``bm``-row block (padding adds an exact 0), then the blocks in
   order — the order the benchmark's float32 reference fixes
   (``transpose_block`` 256).  Across paths the contract stays
   ``allclose`` vs the ref plan / scatter paths, which sum in other
   orders; in slot order the block's edges are grouped by sub-block.
 - SCATTER (oracle, ``qz_reconstruct_batched_bwd``): per edge slot,
   ``(g · vals) (K, bm) @ onehot (bm, window)``.

Both accumulate over the ``j`` (inner) grid dimension into the same
``(K, window)`` grad-z block (revisited-output pattern).  The gathers
that carry f32 values (the composed forward's z, the backward's
cotangents) run at ``Precision.HIGHEST``, so the one-hot selection is
exact on the MXU; the fused draws feed {0,1} masks, exact at any
precision.

Fused mask lifecycle (``qz_sample_reconstruct_*`` /
``qz_sample_pack_*``): the paper's mask ``z ~ Bern(f(s))`` is n BITS.
The fused kernels take the *probability* slab ``p = f(s)`` and draw
``z`` in-block from the counter-based hash RNG
(``core.sampling.mask_u32``: words ``(seed, tensor_id, MASK_CTR, step,
coord)``), so the mask only ever exists as a ``(K, window)`` VMEM value
between the p-window DMA and the one-hot contraction:

 - ``qz_sample_reconstruct_*``: p in, ``w = Q Bern(p)`` out.  The only
   extra operand is the ``(K, 1)`` uint32 ``step`` draw-word column.
   The straight-through backward is UNCHANGED (``grad_p = Q^T
   grad_w``): ``ops.sample_reconstruct`` reuses the composed backward
   kernels, so fused and composed gradients are bit-identical by
   construction.
 - ``qz_sample_pack_*``: the end-of-round upload draw.  p in, ``uint32``
   wire lanes out (bit j of lane i is coordinate 32i+j, exactly
   ``comm.bitpack.pack_mask``); one grid step per z-window emits
   ``window/32`` lanes per client (requires ``window % 32 == 0``;
   smaller windows fall back to the jnp oracle in ``ops``).  The lanes
   are assembled on the MXU as two 16-bit halves (sums of distinct
   powers of two, exact in f32).
 - QUANTIZED operand (``qbits``, the downlink codec subsystem): the
   fused forward also accepts the server's b-bit broadcast words
   (``comm.downlink`` ``u8``/``u16``, or with ``qpacked`` the sub-byte
   codecs' packed uint32 lanes) instead of f32 probabilities — the
   in-block draw becomes the widened-threshold integer compare
   ``(hash >> 8) < q<<(24-b) + (q<<(24-b))//(2^b-1)``, so the
   dequantized f32 score vector never exists in HBM or VMEM.
   Bit-identical to the f32 draw on the codec's decoded probabilities
   (tests/test_downlink.py).

Bit-exactness contract (tests/test_fused.py): fused ≡ composed
(sample → reconstruct → pack) to EXACT equality, forward and gradient,
on ref and on Pallas (interpret mode on the CPU), single-client,
vmap-batched, and the shard_map federated path — both sides regenerate
the identical mask bits from ``(seed, tensor_id, step, coord)``.
tests/test_tpu_compile.py compiles the main-path kernels for a
described TPU v5e at the paper's MNIST-FC widths.

Trace names: the operand and result re-layouts around the kernels
(``_grid_rows_in`` / ``_grid_rows_out`` here, and ``core.reconstruct``'s
moves into and out of the sharding-major row order) run under the
``qz.layout`` named scope (``repro.tracing``), which closes before
each ``pallas_call`` is traced.  No scope encloses a ``pallas_call``: the
kernels' HLO instruction names come from the name stack they are
traced under (``jvp(...)`` / ``transpose(jvp(...))`` of the
``custom_vjp`` in ``kernels.ops``), and the trace readers find them by
those names.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core.hashrng import bernoulli_u32
from ..core.qspec import QSpec, edge_index, edge_value, row_hashes
from ..core.sampling import mask_u32, quant_threshold_u24
from ..core.transpose_plan import build_block_plan
from ..tracing import QZ_LAYOUT

DEFAULT_BM = 256
HIGHEST = jax.lax.Precision.HIGHEST


def _grid_dims(spec: QSpec, bm: int):
    bpw = max(1, math.ceil(spec.rows_per_window / bm))
    return spec.num_windows, bpw, spec.num_windows * bpw * bm  # m_grid


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _block_rows(spec: QSpec, bm: int, shape):
    """Global Q-row ids of grid block (i, j) laid out along ``shape``
    ((1, bm): rows on the lanes; (bm, 1): rows on the sublanes), and
    their liveness (padding rows past ``m`` or past the window's
    ``rows_per_window`` are dead)."""
    local = pl.program_id(1) * bm + _iota(shape, 1 if shape[0] == 1 else 0)
    rows = pl.program_id(0) * spec.rows_per_window + local
    return rows, (rows < spec.m) & (local < spec.rows_per_window)


def _dot(a, b, precision=None):
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision)


def _gather_rows(spec: QSpec, bm: int, zslab, precision):
    """``(K, bm)`` weights of this block: ``Σ_k vals_k · zslab[:, idx_k]``
    over the d edge slots, each gather one (window, bm) one-hot MXU
    contraction, summed in ascending slot order (``qspec.edge_sum``)."""
    rows, _ = _block_rows(spec, bm, (1, bm))
    base, stride = row_hashes(spec, rows)
    coord = _iota((spec.window, bm), 0)
    acc = None
    for k in range(spec.d):
        onehot = (coord == edge_index(spec, base, stride, k)).astype(
            jnp.float32)
        term = edge_value(spec, rows, k) * _dot(zslab, onehot, precision)
        acc = term if acc is None else acc + term
    return acc


def _bfwd_kernel(z_ref, w_ref, *, spec: QSpec, bm: int):
    # z may be any f32 (continuous mode): gather at full precision
    w_ref[...] = _gather_rows(spec, bm, z_ref[...].astype(jnp.float32),
                              HIGHEST)


def _bbwd_kernel(g_ref, gz_ref, *, spec: QSpec, bm: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        gz_ref[...] = jnp.zeros_like(gz_ref)

    rows_l, live = _block_rows(spec, bm, (1, bm))
    rows_s, _ = _block_rows(spec, bm, (bm, 1))
    base, stride = row_hashes(spec, rows_s)
    lane = _iota((bm, spec.window), 1)
    g = jnp.where(live, g_ref[...].astype(jnp.float32), 0.0)  # (K, bm)
    acc = jnp.zeros(gz_ref.shape, jnp.float32)
    for k in range(spec.d):
        onehot = (edge_index(spec, base, stride, k) == lane).astype(
            jnp.float32)
        acc = acc + _dot(g * edge_value(spec, rows_l, k), onehot, HIGHEST)
    gz_ref[...] += acc


@jax.named_scope(QZ_LAYOUT)
def _grid_rows_in(spec: QSpec, grad_W, bm: int):
    """(K, m) cotangents -> the (K, m_grid) per-window padded layout."""
    nclients = grad_W.shape[0]
    nw, bpw, m_grid = _grid_dims(spec, bm)
    g = grad_W.reshape(nclients, -1).astype(jnp.float32)
    g = jnp.pad(g, ((0, 0), (0, spec.m_pad - spec.m)))
    if bpw * bm != spec.rows_per_window:
        g = g.reshape(nclients, nw, spec.rows_per_window)
        g = jnp.pad(g, ((0, 0), (0, 0),
                        (0, bpw * bm - spec.rows_per_window)))
    return g.reshape(nclients, m_grid)


@jax.named_scope(QZ_LAYOUT)
def _grid_rows_out(spec: QSpec, out, bm: int):
    """(K, m_grid) kernel rows -> (K, m) (drop the per-window padding)."""
    nw, bpw, _ = _grid_dims(spec, bm)
    if bpw * bm != spec.rows_per_window:
        out = out.reshape(out.shape[0], nw, bpw * bm)[
            :, :, : spec.rows_per_window
        ].reshape(out.shape[0], -1)
    return out[:, : spec.m]


def _w_out(spec: QSpec, nclients: int, bm: int):
    """Out spec + shape of a (K, m_grid) weight-row kernel."""
    _, bpw, m_grid = _grid_dims(spec, bm)
    return (pl.BlockSpec((nclients, bm), lambda i, j: (0, i * bpw + j)),
            jax.ShapeDtypeStruct((nclients, m_grid), jnp.float32))


def _gz_out(spec: QSpec, nclients: int):
    """Out spec + shape of a (K, n) grad-z kernel (window i revisited)."""
    return (pl.BlockSpec((nclients, spec.window), lambda i, j: (0, i)),
            jax.ShapeDtypeStruct((nclients, spec.n), jnp.float32))


def qz_reconstruct_batched_fwd(spec: QSpec, Z, *, bm: int = DEFAULT_BM,
                               interpret: bool = False):
    """Batched Pallas forward: Z (K, n) f32 -> W (K, m) f32 (flat)."""
    nclients = Z.shape[0]
    nw, bpw, _ = _grid_dims(spec, bm)
    out_spec, out_shape = _w_out(spec, nclients, bm)
    out = pl.pallas_call(
        functools.partial(_bfwd_kernel, spec=spec, bm=bm),
        grid=(nw, bpw),
        in_specs=[pl.BlockSpec((nclients, spec.window),
                               lambda i, j: (0, i))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(Z.astype(jnp.float32))
    return _grid_rows_out(spec, out, bm)


def qz_reconstruct_batched_bwd(spec: QSpec, grad_W, *, bm: int = DEFAULT_BM,
                               interpret: bool = False):
    """Batched scatter backward: grad_W (K, m) -> grad_Z (K, n) f32."""
    nclients = grad_W.shape[0]
    nw, bpw, _ = _grid_dims(spec, bm)
    out_spec, out_shape = _gz_out(spec, nclients)
    return pl.pallas_call(
        functools.partial(_bbwd_kernel, spec=spec, bm=bm),
        grid=(nw, bpw),
        in_specs=[pl.BlockSpec((nclients, bm),
                               lambda i, j: (0, i * bpw + j))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(_grid_rows_in(spec, grad_W, bm))


def qz_reconstruct_fwd(spec: QSpec, z, *, bm: int = DEFAULT_BM,
                       interpret: bool = False):
    """Pallas forward: z (n,) f32 -> w (m,) f32 (flat; caller reshapes)."""
    return qz_reconstruct_batched_fwd(spec, z[None], bm=bm,
                                      interpret=interpret)[0]


def qz_reconstruct_bwd(spec: QSpec, grad_w, *, bm: int = DEFAULT_BM,
                       interpret: bool = False):
    """Pallas scatter backward: grad_w (m,) -> grad_z (n,) f32."""
    return qz_reconstruct_batched_bwd(spec, grad_w.reshape(1, -1), bm=bm,
                                      interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Plan-driven backward: the transpose as an in-block GATHER over the
# cached block plan (see module docstring and core.transpose_plan).
# ---------------------------------------------------------------------------

def _bbwd_plan_kernel(g_ref, rows_ref, vals_ref, gz_ref, *, spec: QSpec,
                      sub: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        gz_ref[...] = jnp.zeros_like(gz_ref)

    g = g_ref[...].astype(jnp.float32)  # (K, bm)
    rows = rows_ref[0, 0]  # (nsub, deg, window) sub-block-relative rows
    vals = vals_ref[0, 0]
    nsub, deg, _ = rows.shape
    src = _iota((sub, spec.window), 0)
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    # one chain per block: sub-block 0's slots, then sub-block 1's, ...
    # — each coordinate's edges in the block plan's order (padding adds
    # an exact 0), the same sums as one block-high one-hot per slot
    acc = jnp.zeros(gz_ref.shape, jnp.float32)
    for b in range(nsub):
        g_b = g[:, b * sub:(b + 1) * sub]
        for e in range(deg):
            onehot = jnp.where(src == rows[b, e:e + 1, :], one, zero)
            acc = acc + vals[b, e:e + 1, :] * _dot(g_b, onehot, HIGHEST)
    gz_ref[...] += acc


def _bwd_plan_call(spec: QSpec, grad_W, plan, *, interpret: bool):
    """The plan backward's ``pallas_call`` over a built ``BlockPlan``:
    grid (num_windows, blocks_per_window) of ``plan.bm`` rows, each
    step reading its (nsub, deg, window) sub-plans."""
    nclients, bm = grad_W.shape[0], plan.bm
    nw, bpw, _ = _grid_dims(spec, bm)
    bspec = pl.BlockSpec((1, 1) + plan.rows.shape[2:],
                         lambda i, j: (i, j, 0, 0, 0))
    out_spec, out_shape = _gz_out(spec, nclients)
    return pl.pallas_call(
        functools.partial(_bbwd_plan_kernel, spec=spec, sub=plan.sub),
        grid=(nw, bpw),
        in_specs=[
            pl.BlockSpec((nclients, bm), lambda i, j: (0, i * bpw + j)),
            bspec, bspec,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(_grid_rows_in(spec, grad_W, bm), jnp.asarray(plan.rows),
      jnp.asarray(plan.vals))


def qz_reconstruct_batched_bwd_plan(spec: QSpec, grad_W, *,
                                    bm: int = DEFAULT_BM,
                                    interpret: bool = False,
                                    order: str = "canonical"):
    """Plan-driven batched backward: grad_W (K, m) -> grad_Z (K, n)."""
    return _bwd_plan_call(spec, grad_W, build_block_plan(spec, bm, order),
                          interpret=interpret)


def qz_reconstruct_bwd_plan(spec: QSpec, grad_w, *, bm: int = DEFAULT_BM,
                            interpret: bool = False,
                            order: str = "canonical"):
    """Plan-driven Pallas backward: grad_w (m,) -> grad_z (n,) f32."""
    return qz_reconstruct_batched_bwd_plan(
        spec, grad_w.reshape(1, -1), bm=bm, interpret=interpret,
        order=order)[0]


# ---------------------------------------------------------------------------
# Fused mask lifecycle: probabilities in, weights / wire lanes out.
# The mask z is a transient in-block value, never an HBM array.
# ---------------------------------------------------------------------------

def _lanes_per_window(spec: QSpec, qbits: int) -> int:
    """Packed-operand block length: uint32 lanes per z-window.  The
    packed fused path needs whole lanes per window (lane i covers
    coordinates [i·wpl, (i+1)·wpl)), i.e. ``window % floor(32/b) == 0``
    — true for every power-of-two b at the standard window sizes;
    ``ops`` falls back to the unpack oracle otherwise."""
    wpl = 32 // qbits
    if spec.window % wpl != 0:
        raise ValueError(
            f"packed fused kernel needs window % (32//qbits) == 0; got "
            f"window={spec.window}, qbits={qbits} (wpl={wpl})"
        )
    return spec.window // wpl


def _unpack_window(spec: QSpec, lanes, qbits: int):
    """In-block lane unpack: (K, window/wpl) uint32 lanes -> (K, window)
    b-bit words — a VMEM-local shift/mask, so the per-coordinate word
    array only ever exists as this window-sized transient, never as an
    (n,) slab in HBM (jaxpr-asserted in tests/test_packed_downlink.py).
    Word s of every lane is spread to coordinates ``lane·wpl + s`` by
    one one-hot MXU contraction per s (exact: b ≤ 16-bit integers at
    full precision)."""
    wpl = 32 // qbits
    nl = lanes.shape[-1]
    coord = _iota((nl, spec.window), 1)
    own = (coord // wpl) == _iota((nl, spec.window), 0)
    mask = np.uint32((1 << qbits) - 1)
    words = jnp.zeros((lanes.shape[0], spec.window), jnp.float32)
    for s in range(wpl):
        part = ((lanes >> np.uint32(s * qbits)) & mask).astype(jnp.int32)
        spread = (own & (coord % wpl == s)).astype(jnp.float32)
        words = words + _dot(part.astype(jnp.float32), spread, HIGHEST)
    return words.astype(jnp.int32).astype(jnp.uint32)


def _window_mask(spec: QSpec, steps, p_win, qbits=None, qpacked=False,
                 window_id=None):
    """Draw grid window ``window_id``'s (default ``program_id(0)``)
    z-bits in-block from the hash RNG: ``steps`` (K, 1) uint32 draw
    words, ``p_win`` the (K, window) operand -> (K, window) f32 {0,1}.

    Coordinates are the window's global z indices, so the bits are
    identical to the oracle's ``sample_mask_hash`` over the full (n,)
    vector.  With ``qbits`` the operand is the QUANTIZED probability
    window (uint32 b-bit words from the downlink codec,
    ``comm.downlink``) and the draw is the widened-threshold integer
    compare ``(u >> 8) < quant_threshold_u24(q)`` — bit-identical to
    the oracle's ``sample_mask_qhash``.  With ``qpacked`` the operand
    window is the packed uint32 LANES of the sub-byte codecs
    (``comm.bitpack.pack_words`` layout), unpacked in-block first.
    """
    if qpacked:
        p_win = _unpack_window(spec, p_win, qbits)
    if window_id is None:
        window_id = pl.program_id(0)
    coords = window_id * spec.window + _iota((1, spec.window), 1)
    u = mask_u32(spec.seed, spec.tensor_id, steps, coords)
    if qbits is None:
        return bernoulli_u32(u, p_win.astype(jnp.float32))
    thr = quant_threshold_u24(p_win, qbits)
    return ((u >> np.uint32(8)) < thr).astype(jnp.float32)


def _sbfwd_kernel(p_ref, steps_ref, w_ref, *, spec: QSpec, bm: int,
                  qbits=None, qpacked=False):
    p_win = p_ref[0] if qpacked else p_ref[...]
    z = _window_mask(spec, steps_ref[...], p_win, qbits=qbits,
                     qpacked=qpacked)  # (K, window) {0,1}: exact gather
    w_ref[...] = _gather_rows(spec, bm, z, None)


def _operand(spec: QSpec, P, qbits, qpacked):
    """The fused forward's probability operand and its BlockSpec.

    f32 probabilities, or the codec's words widened to uint32, as the
    (K, n) slab read one (K, window) block per window.  The packed lane
    carry is laid out (num_windows, K, window/wpl), so each block is a
    whole (K, window/wpl) tile however few lanes a window holds.
    """
    nclients = P.shape[0]
    if qbits is None:
        P = jnp.asarray(P).astype(jnp.float32)
    else:
        P = jnp.asarray(P).astype(jnp.uint32)
    if not qpacked:
        return P, pl.BlockSpec((nclients, spec.window), lambda i, j: (0, i))
    nl = _lanes_per_window(spec, qbits)
    P = jnp.transpose(P.reshape(nclients, spec.num_windows, nl), (1, 0, 2))
    return P, pl.BlockSpec((1, nclients, nl), lambda i, j: (i, 0, 0))


def _steps_col(steps, nclients: int):
    """The (K, 1) uint32 draw-word column every fused kernel reads."""
    return jnp.broadcast_to(jnp.asarray(steps, jnp.uint32).reshape(-1, 1),
                            (nclients, 1))


def qz_sample_reconstruct_batched_fwd(spec: QSpec, P, steps, *,
                                      bm: int = DEFAULT_BM,
                                      interpret: bool = False, qbits=None,
                                      qpacked=False):
    """Fused batched forward: P (K, n) probs + steps (K,) -> W (K, m).

    With ``qbits`` P is the (K, n) quantized word slab (b-bit
    probability words, shipped into the kernel as uint32) and the
    in-block draw is the widened-threshold integer compare — the
    dequantized f32 score vector never exists, in HBM or VMEM.  With
    ``qpacked`` P is the (K, n/wpl) packed uint32 LANE slab of the
    sub-byte codecs and each grid step streams ``window/wpl`` whole
    lanes, unpacking in-block.
    """
    nclients = P.shape[0]
    nw, bpw, _ = _grid_dims(spec, bm)
    operand, op_spec = _operand(spec, P, qbits, qpacked)
    out_spec, out_shape = _w_out(spec, nclients, bm)
    out = pl.pallas_call(
        functools.partial(_sbfwd_kernel, spec=spec, bm=bm, qbits=qbits,
                          qpacked=qpacked),
        grid=(nw, bpw),
        in_specs=[op_spec, pl.BlockSpec((nclients, 1), lambda i, j: (0, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(operand, _steps_col(steps, nclients))
    return _grid_rows_out(spec, out, bm)


def qz_sample_reconstruct_fwd(spec: QSpec, p, step, *, bm: int = DEFAULT_BM,
                              interpret: bool = False, qbits=None,
                              qpacked=False):
    """Fused Pallas forward: p (n,) + step word -> w (m,) f32 (flat);
    ``qbits``/``qpacked`` as ``qz_sample_reconstruct_batched_fwd``."""
    return qz_sample_reconstruct_batched_fwd(
        spec, jnp.asarray(p)[None], step, bm=bm, interpret=interpret,
        qbits=qbits, qpacked=qpacked)[0]


def _pack_lanes(bits, window: int):
    """(K, window) f32 {0,1} -> (K, window/32) uint32 lanes, bit j of
    lane i = coordinate 32i+j.  Each 16-bit half is one MXU contraction
    against a (window, window/32) matrix of distinct powers of two, so
    every sum is an exact integer below 2^16."""
    shape = (window, window // 32)
    coord = _iota(shape, 0)
    own = (coord // 32) == _iota(shape, 1)
    bit = coord % 32

    def half(lo: int):
        sel = own & (bit >= lo) & (bit < lo + 16)
        weight = jnp.where(sel, jnp.left_shift(1, (bit - lo) & 15), 0)
        return _dot(bits, weight.astype(jnp.float32)).astype(jnp.int32)

    word = half(0) | jnp.left_shift(half(16), 16)
    return jax.lax.bitcast_convert_type(word, jnp.uint32)


def _sbpack_kernel(p_ref, steps_ref, lanes_ref, *, spec: QSpec):
    z = _window_mask(spec, steps_ref[...], p_ref[...].astype(jnp.float32))
    lanes_ref[0] = _pack_lanes(z, spec.window)


def qz_sample_pack_batched_fwd(spec: QSpec, P, steps, *,
                               interpret: bool = False):
    """Fused batched upload draw: P (K, n) -> (K, n/32) uint32 lanes.

    Lane layout is exactly ``comm.bitpack.pack_mask``.  Requires
    ``spec.window % 32 == 0`` so each grid step emits whole lanes
    (``ops.sample_pack`` falls back to the jnp oracle otherwise).  The
    kernel writes a (num_windows, K, window/32) slab — one full
    (K, window/32) tile per grid step — that the wrapper lays out as
    (K, n/32).
    """
    assert spec.window % 32 == 0, "pallas sample_pack needs window % 32 == 0"
    nclients = P.shape[0]
    nl = spec.window // 32
    out = pl.pallas_call(
        functools.partial(_sbpack_kernel, spec=spec),
        grid=(spec.num_windows,),
        in_specs=[
            pl.BlockSpec((nclients, spec.window), lambda i: (0, i)),
            pl.BlockSpec((nclients, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nclients, nl), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((spec.num_windows, nclients, nl),
                                       jnp.uint32),
        interpret=interpret,
    )(jnp.asarray(P).astype(jnp.float32), _steps_col(steps, nclients))
    return jnp.transpose(out, (1, 0, 2)).reshape(nclients, spec.n // 32)


def qz_sample_pack_fwd(spec: QSpec, p, step, *, interpret: bool = False):
    """Fused upload draw: p (n,) -> (n/32,) uint32 wire lanes."""
    return qz_sample_pack_batched_fwd(spec, jnp.asarray(p)[None], step,
                                      interpret=interpret)[0]
