"""QSpec — static description of one tensor's influence matrix Q.

Paper (§1.3): ``Q ∈ R^{m×n}`` has exactly ``d`` non-zeros per row, drawn
``N(0, 6/(d·fan_in))``; ``w = Q z`` with ``z ~ Bern(p)``.

TPU adaptation (DESIGN.md §3): indices for row ``i`` are drawn from a
contiguous *window* of ``z`` of size ``window`` (a power of two) assigned
by ``i // rows_per_window``, so a Pallas block keeps its window resident
in VMEM.  Distinctness of the ``d`` indices is guaranteed structurally:

    idx_k = (base + k * stride) mod window,   stride odd, window = 2^t

an odd stride is a unit of Z/2^t, so the d < window points are distinct —
this replaces the paper's "sample d indices without replacement" with an
equivalent-marginal, two-hashes-per-row scheme.

Nothing here allocates: QSpec is a hashable static pytree-leaf-free
dataclass, usable as a closure constant under ``jit``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import numpy as np
import jax.numpy as jnp

from .hashrng import gaussian_from_u32, hash_u32

# Counter-space roles for hash_u32(seed, tensor_id, row, ctr).
_CTR_BASE = 0x0001_0000
_CTR_STRIDE = 0x0002_0000
_CTR_VAL = 0x0004_0000  # value k uses counters _CTR_VAL + 2k, +2k+1


@dataclass(frozen=True)
class QSpec:
    """Static (hashable) spec of one tensor's sparse influence matrix.

    Distribution-aware layout (DESIGN.md §3, "sharding-major rows"):
    the tensor is flattened with ``major_axis`` moved to the front, and
    rows/windows are grouped into ``shard_count`` contiguous blocks so
    that block k's rows read ONLY block k's z windows.  With
    shape[major_axis] % shard_count == 0, the reconstruction emits the
    tensor already sharded on its consumer axis — no reshard, no
    replicated intermediates.  shard_count=1 (default) is the plain
    single-host layout used by the paper-scale experiments and tests.
    """

    tensor_id: int
    shape: tuple  # original weight tensor shape
    m: int  # number of weights = prod(shape)
    n: int  # trainable-parameter count (padded to num_windows*window)
    n_raw: int  # ceil(m / compression) before window padding
    d: int  # non-zeros per row
    window: int  # z-window size (power of two)
    num_windows: int
    rows_per_window: int
    m_pad: int  # shard_count * m_pad_loc >= m
    fan_in: int  # fan-in of the target neuron (sets sigma)
    seed: int
    major_axis: int = 0  # tensor axis that shards (moved to front)
    shard_count: int = 1  # contiguous row/window blocks (mesh model size)

    @property
    def sigma(self) -> float:
        return math.sqrt(6.0 / (self.d * max(self.fan_in, 1)))

    @property
    def compression(self) -> float:
        """Achieved compression factor m/n."""
        return self.m / self.n

    # --- layout helpers -------------------------------------------------
    @property
    def m_blk(self) -> int:
        return self.m // self.shard_count

    @property
    def nw_loc(self) -> int:
        return self.num_windows // self.shard_count

    @property
    def m_pad_loc(self) -> int:
        return self.nw_loc * self.rows_per_window

    @property
    def moved_shape(self) -> tuple:
        a = self.major_axis
        return (self.shape[a], *self.shape[:a], *self.shape[a + 1:])


def make_qspec(
    tensor_id: int,
    shape,
    fan_in: int,
    *,
    compression: float = 32.0,
    d: int = 8,
    window: int = 512,
    seed: int = 0,
    align: int = 1,
    major_axis: int = 0,
    shard_count: int = 1,
) -> QSpec:
    """Build a QSpec for a weight tensor.

    ``n`` is rounded up so the z vector tiles exactly into power-of-two
    windows; the achieved compression (``spec.compression``) is reported
    rather than silently pretending the requested one.

    ``align``: round num_windows up to a multiple of this (the mesh
    'model' axis size), so z and the (num_windows, rows_per_window) row
    space shard contiguously with window-local gathers (DESIGN.md §3.2).
    """
    shape = tuple(int(s) for s in shape)
    m = int(math.prod(shape))
    major_axis = int(major_axis)
    shard_count = int(shard_count)
    if shard_count > 1 and (shape[major_axis] % shard_count
                            or m % shard_count):
        # axis not block-shardable: fall back to the single-block layout
        major_axis, shard_count = 0, 1
    n_raw = max(1, math.ceil(m / compression))
    window = int(min(window, 1 << max(1, math.ceil(math.log2(max(n_raw, 2))))))
    if window & (window - 1):
        raise ValueError(f"window must be a power of two, got {window}")
    if d >= window:
        d = max(1, window // 2)
    align = max(align, shard_count)
    num_windows = max(1, math.ceil(n_raw / window))
    num_windows = math.ceil(num_windows / align) * align
    n = num_windows * window
    nw_loc = num_windows // shard_count
    m_blk = m // shard_count
    rows_per_window = math.ceil(m_blk / nw_loc)
    m_pad = rows_per_window * nw_loc * shard_count
    return QSpec(
        tensor_id=int(tensor_id),
        shape=shape,
        m=m,
        n=n,
        n_raw=n_raw,
        d=int(d),
        window=window,
        num_windows=num_windows,
        rows_per_window=rows_per_window,
        m_pad=m_pad,
        fan_in=int(fan_in),
        seed=int(seed),
        major_axis=major_axis,
        shard_count=shard_count,
    )


def padded_row_window(spec: QSpec, rp):
    """Padded row id -> global window id (shard-block aware)."""
    blk = rp // spec.m_pad_loc
    loc = rp % spec.m_pad_loc
    return (blk * spec.nw_loc
            + jnp.minimum(loc // spec.rows_per_window, spec.nw_loc - 1)
            ).astype(jnp.int32)


def padded_row_valid(spec: QSpec, rp):
    """True where a padded row id maps to a real weight."""
    return (rp % spec.m_pad_loc) < spec.m_blk


def row_hashes(spec: QSpec, rows):
    """(base, stride) uint32 words of the given row ids, shaped like
    ``rows``: edge k of a row sits at in-window column
    ``(base + k·stride) mod window`` (``edge_index``)."""
    rows = jnp.asarray(rows).astype(jnp.uint32)
    base = hash_u32(spec.seed, spec.tensor_id, rows, _CTR_BASE) & np.uint32(
        spec.window - 1
    )
    # stride odd in [1, window): unit mod 2^t => the d points are distinct
    stride = (
        hash_u32(spec.seed, spec.tensor_id, rows, _CTR_STRIDE)
        % np.uint32(spec.window // 2)
    ) * np.uint32(2) + np.uint32(1)
    return base, stride


def edge_index(spec: QSpec, base, stride, k):
    """In-window column of edge ``k`` (int32, shaped like base/k)."""
    return ((base + stride * k) & np.uint32(spec.window - 1)).astype(
        jnp.int32)


def edge_value(spec: QSpec, rows, k, dtype=jnp.float32):
    """Gaussian coefficient of edge ``k`` of each row, shaped like
    ``rows`` broadcast against ``k`` (a static int or a uint32 array)."""
    rows = jnp.asarray(rows).astype(jnp.uint32)
    ua = hash_u32(spec.seed, spec.tensor_id, rows, _CTR_VAL + 2 * k)
    ub = hash_u32(spec.seed, spec.tensor_id, rows, _CTR_VAL + 2 * k + 1)
    g = gaussian_from_u32(ua, ub) * np.float32(spec.sigma)
    return g.astype(dtype)


def edge_sum(terms):
    """Sum the trailing edge-slot axis in ascending slot order.

    THE reduction order of ``w = Σ_k q_k z_idx_k`` for every path (ref,
    chunked, sharded, serve, and the Pallas kernels, which accumulate
    slot by slot): a fused XLA reduce picks its own, context-dependent
    order, so bit-identical paths spell the order out.
    """
    acc = terms[..., 0]
    for k in range(1, terms.shape[-1]):
        acc = acc + terms[..., k]
    return acc


def row_indices(spec: QSpec, rows):
    """In-window column indices for the given (global) row ids.

    Returns int32 ``(..., d)`` in ``[0, window)``; the global z index is
    ``(rows // rows_per_window) * window + idx``.
    """
    base, stride = row_hashes(spec, rows)
    k = jnp.arange(spec.d, dtype=jnp.uint32)
    return edge_index(spec, base[..., None], stride[..., None], k)


def row_values(spec: QSpec, rows, dtype=jnp.float32):
    """Gaussian coefficients ``q_{i,k} ~ N(0, 6/(d·fan_in))``, shape (..., d)."""
    rows = jnp.asarray(rows).astype(jnp.uint32)
    k = jnp.arange(spec.d, dtype=jnp.uint32)
    return edge_value(spec, rows[..., None], k, dtype)
