"""Pallas decode kernels: fused ``y = x @ (Q Bern(f(s)))`` — serving
without weights.

``qz_reconstruct`` turned the mask lifecycle into in-kernel draws but
still EMITS the (m,) weight tensor; a serving fleet then holds full
f32 weights resident per model, which is exactly the memory the
paper's (seed, z) story promised back.  These kernels go one step
further: the decode-path contraction consumes the weight values the
moment they are regenerated, so the only resident zampled state is the
encoded score broadcast (u8/u16 words, or f32 scores) and the only
weight values that ever exist live in VMEM for one block.

Per (window, bm) grid block, for the submatrix ``W_g = rows
[row_offset, row_offset + d_in*d_out)`` of the spec's flat moved row
space (``group`` selects a stacked layer; 2-D leaves have one group):

 - regenerate the block's Q edges from the counter-hash RNG
   (``core.qspec.row_hashes`` / ``edge_value`` — identical streams to
   every other kernel) and sum each row's d edge terms in ascending
   slot order (``core.qspec.edge_sum``);
 - draw the z-window in-block from the encoded score words: f32 scores
   via ``bernoulli_u32``, quantized words via the widened-threshold
   integer compare ``(u >> 8) < quant_threshold_u24(q)`` (the PR-5
   downlink codec contract, ``comm.downlink``) — the decoded f32 score
   vector never exists anywhere;
 - scatter the block's ``bm`` weight values into the canonical
   i-aligned tile: flat row ``r`` maps to cell ``(i - i_lo, o)`` of a
   (NI, d_out) tile with ``i = (r - row_offset) // d_out``,
   ``o = (r - row_offset) % d_out``, ``i_lo`` the block's first input
   row and ``NI = bm // d_out + 2`` static (each cell is one term, so
   the scatter is exact);
 - accumulate ``y += x[i_lo : i_lo + NI] @ tile`` into the revisited
   (B, d_out) output that stays in VMEM across the grid
   (zero-initialized at grid step (0, 0)); a matvec is the B=1 case.

Exactness contract: the kernels replay ``kernels.ops``'s CANONICAL
CONTRACTION TREE (see the serve section comment there) — identical
tile shapes, operand values, and ascending (window, block) add order
as the ref/chunked impls — so the result is bit-identical to
``reconstruct``-then-(canonically tiled)-matmul by construction, up
to IEEE signed zeros in all-dead tile cells (XLA's own dot reduction
tree is context-dependent, which is why the tree is pinned explicitly
rather than inherited from one big ``jnp.dot``).  Verified in
tests/test_serve.py: exact equality, all three codecs, single and
batched, interpret-mode Pallas vs both jnp fallbacks.

Layout: every in-kernel array is 2-D.  The block's rows sit on the
sublanes for the edge gathers (one (bm, window) one-hot per edge slot),
the tile's row one-hot is built transposed (NI, bm) from the rows laid
on the lanes, and the x-segment selection is an ``x @ onehot^T``
contraction — so no reshape crosses lanes.  The dots
that move f32 values (tile scatter, x selection, the contraction) run
at ``Precision.HIGHEST``; on the CPU precision is moot and the tree is
bit-identical to ``ops``.

VMEM note: the scatter one-hots are (bm, NI), (bm, d_out), and
(NI, d_in) f32 — at bm=256 and LLM vocab widths the (bm, d_out)
one-hot dominates; wide outputs want a blocked d_out grid axis.

Grid: only the windows overlapping the group's row range run —
``w0 = row_offset // rows_per_window`` is folded into the p-window
BlockSpec, so a stacked leaf costs one layer's blocks per call, not L.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.qspec import QSpec, edge_index, edge_value, row_hashes
from .ops import SERVE_BM, serve_block_grid, serve_tile_rows
from .qz_reconstruct import (
    HIGHEST,
    _dot,
    _iota,
    _lanes_per_window,
    _window_mask,
)


def _decode_block(p_ref, step_ref, *, spec: QSpec, bm: int, w0: int,
                  row_offset: int, d_in: int, d_out: int, qbits,
                  qpacked=False):
    """Shared front half of the decode kernel.

    Regenerates this block's weight values and scatters them into the
    canonical (NI, d_out) tile.  Returns (tile, oh_x) with ``oh_x``
    the (NI, d_in) one-hot selecting ``x[i_lo : i_lo + NI]`` (zero
    rows past d_in), matching ``ops._serve_contract_blocks``'s padded
    dynamic slice value-for-value.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    rpw = spec.rows_per_window
    bstart = (w0 + i) * rpw + j * bm
    sub = d_in * d_out

    def rows_live(shape, axis):
        lane = _iota(shape, axis)
        rows = bstart + lane
        live = ((rows >= row_offset) & (rows < row_offset + sub)
                & (j * bm + lane < rpw) & (rows < spec.m))
        return rows, live

    rows_s, live_s = rows_live((bm, 1), 0)  # rows on the sublanes
    rows_l, live_l = rows_live((1, bm), 1)  # rows on the lanes
    z = _window_mask(spec, step_ref[...], p_ref[0], qbits=qbits,
                     qpacked=qpacked, window_id=w0 + i)  # (1, window)
    base, stride = row_hashes(spec, rows_s)
    coord = _iota((bm, spec.window), 1)
    w_blk = None  # (bm, 1), summed in ascending slot order (edge_sum)
    for k in range(spec.d):
        onehot = (edge_index(spec, base, stride, k) == coord).astype(
            jnp.float32)
        term = edge_value(spec, rows_s, k) * jnp.sum(
            onehot * z, axis=1, keepdims=True)  # one live term: exact
        w_blk = term if w_blk is None else w_blk + term
    w_blk = jnp.where(live_s, w_blk, 0.0)
    ni = serve_tile_rows(bm, d_out)
    i_lo = jnp.clip(bstart - row_offset, 0, sub - 1) // d_out
    a_rows = jnp.where(live_l, (rows_l - row_offset) // d_out - i_lo, ni)
    o_cols = jnp.where(live_s, (rows_s - row_offset) % d_out, 0)
    oh_a = (_iota((ni, bm), 0) == a_rows).astype(jnp.float32)  # (ni, bm)
    oh_o = (o_cols == _iota((bm, d_out), 1)).astype(jnp.float32)
    tile = _dot(oh_a, w_blk * oh_o, HIGHEST)  # (ni, d_out)
    oh_x = ((i_lo + _iota((ni, d_in), 0)) == _iota((ni, d_in), 1)
            ).astype(jnp.float32)  # (ni, d_in)
    return tile, oh_x


def _mm_kernel(p_ref, step_ref, x_ref, y_ref, *, spec: QSpec, bm: int,
               w0: int, row_offset: int, d_in: int, d_out: int, qbits,
               qpacked=False):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    tile, oh_x = _decode_block(
        p_ref, step_ref, spec=spec, bm=bm, w0=w0, row_offset=row_offset,
        d_in=d_in, d_out=d_out, qbits=qbits, qpacked=qpacked,
    )
    xseg = jax.lax.dot_general(  # x @ oh_x^T: (B, ni)
        x_ref[...].astype(jnp.float32), oh_x, (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
    y_ref[...] += _dot(xseg, tile, HIGHEST)


def _check_layout(spec: QSpec, row_offset: int, d_in: int, d_out: int):
    if spec.shard_count != 1:
        raise ValueError(
            "decode kernels address the single-block row layout; "
            f"spec has shard_count={spec.shard_count}"
        )
    if row_offset + d_in * d_out > spec.m:
        raise ValueError(
            f"group rows [{row_offset}, {row_offset + d_in * d_out}) "
            f"exceed spec.m={spec.m}"
        )


def qz_sample_matmul(spec: QSpec, p, step, X, *, row_offset: int = 0,
                     d_in: int, d_out: int, qbits=None, qpacked=False,
                     bm: int = SERVE_BM, interpret: bool = False):
    """Fused serve matmul: encoded scores + X (B, d_in) -> (B, d_out) f32.

    ``p``: the (n,) score operand — CLIPPED f32 probabilities
    (``qbits=None``), the codec's uint words (``qbits=b``), or with
    ``qpacked`` the (n/wpl,) packed uint32 lane carry.  ``step`` is the
    uint32 draw word pinning the mask draw.  The batch rides in-block as
    extra rows of the x-segment selection (the same K-columns-for-free
    trade as the batched reconstruct kernels).  Bit-identical to
    ``ops.serve_matmul`` on every impl (the canonical tree) for rows
    [row_offset, row_offset + d_in*d_out).
    """
    _check_layout(spec, row_offset, d_in, d_out)
    w0, nblk, bpw = serve_block_grid(spec, bm, row_offset, d_in * d_out)
    B = X.shape[0]
    op_len = _lanes_per_window(spec, qbits) if qpacked else spec.window
    operand = (p.astype(jnp.float32) if qbits is None
               else jnp.asarray(p).astype(jnp.uint32))
    return pl.pallas_call(
        functools.partial(_mm_kernel, spec=spec, bm=bm, w0=w0,
                          row_offset=row_offset, d_in=d_in, d_out=d_out,
                          qbits=qbits, qpacked=qpacked),
        grid=(nblk // bpw, bpw),
        in_specs=[
            pl.BlockSpec((1, 1, op_len), lambda i, j: (w0 + i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((B, d_in), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, d_out), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, d_out), jnp.float32),
        interpret=interpret,
    )(operand.reshape(spec.num_windows, 1, op_len),
      jnp.asarray(step, jnp.uint32).reshape(1, 1), X.astype(jnp.float32))


def qz_sample_matvec(spec: QSpec, p, step, x, **kw):
    """Fused serve matvec: x (d_in,) -> y (d_out,) — the B=1 matmul
    (the canonical tree contracts a matvec as a one-row matmul)."""
    return qz_sample_matmul(spec, p, step, x[None], **kw)[0]
