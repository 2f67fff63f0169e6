"""jit'd public wrappers around the reconstruction kernels.

``reconstruct(spec, z)`` is THE hot op of the paper's technique: every
training/serving step turns the sampled mask ``z`` back into weights.
Dispatch:

 - impl='ref'     pure-jnp oracle (the default on every backend)
 - impl='pallas'  the Pallas TPU kernels, compiled on a TPU and run by
                  the Pallas interpreter on the CPU (``_interpret``);
                  single-block layout, shard_count == 1
 - distributed    when the spec carries shard_count > 1 and a mesh is
                  active, the manually-partitioned shard_map op emits
                  the tensor directly in consumer sharding
                  (kernels.qz_sharded — zero collectives)
 - chunks>1       lax.map over row-chunks of the ref path (bounds the
                  O(m·d) temporaries on a single host)

A ``jax.custom_vjp`` ties forward and backward together so both
directions use the same impl and the straight-through chain
``grad_s = Q^T grad_w ⊙ 1_{0<p<1}`` (paper §1.3) falls out of autodiff.

Transpose path: every backward branch (ref, chunked, pallas, sharded)
additionally dispatches plan-vs-scatter via
``core.transpose_plan.resolve_bwd_path()`` (env ``REPRO_BWD_PLAN``,
read at trace time; the custom_vjp/custom_vmap signatures are
unchanged).  'plan' (default) computes ``grad_z = Q^T grad_w`` as a
gather + reduction over the cached per-spec transpose plan — measured
>2x over the scatter oracle at K∈{10,32} on the CPU ref path
(``bwd_transpose_plan`` rows in BENCH_reconstruct.json); 'scatter' is
the bit-exactness oracle.  The chunked plan path chunks over WINDOWS
(each chunk owns a contiguous ``g_pad`` slice) instead of rows,
bounding temporaries at O(n·deg/chunks).

Batching-aware dispatch: every impl above also has a natively-batched
variant that takes ``Z (K, n)`` (K stacked clients) and regenerates
Q's hash-RNG indices/values ONCE instead of per client —
``reconstruct_batched`` is the explicit entry point.  On top of that,
the single-client op's custom_vjp internals are wrapped in
``jax.custom_batching.custom_vmap`` rules (one for the forward, one
for the cotangent), so ``jax.vmap(local_update)`` in
``core.federated`` lowers onto the batched kernels automatically —
including under ``vmap(grad(...))``, where JAX batches the stored fwd
and bwd jaxprs separately and hits one rule in each.  The backward
rule accumulates ``grad_Z = Q^T grad_W`` per client.  Benchmarks
(benchmarks/run.py bench_federated_round; BENCH_reconstruct.json at
the repo root) track the batched-vs-vmap win: ~4x at K=10 and ~5x
at K=32 on the CPU ref path (forward; the backward scatter batches
well under plain vmap and stays at parity), where the hash+Box-Muller regeneration
dominates a single-client reconstruct.

Fused mask lifecycle: ``sample_reconstruct`` (+``_batched``) computes
``w = Q·Bern(p)`` with the Bernoulli draw INSIDE the op — probs in,
weights out, the mask a transient value keyed by the uint32 ``step``
draw word (``core.sampling.mask_u32``).  Its custom_vjp backward is
the straight-through ``grad_p = Q^T grad_w`` — literally the composed
op's backward cores — so fused ≡ composed to exact equality, forward
and gradient, per impl (tests/test_fused.py).  ``sample_pack``
(+``_batched``) is the end-of-round upload draw: probs in, uint32
wire lanes out (``comm.bitpack.pack_mask`` layout), fed natively to
the packed transports.  Both carry the same custom_vmap rules as the
composed ops.  ``sample_reconstruct(..., qbits=b)`` additionally
accepts the QUANTIZED downlink broadcast (the ``comm.downlink``
codec's b-bit probability words): the in-op draw is the
widened-threshold integer compare (``core.sampling
.sample_mask_qhash``), bit-identical to the f32 draw on the decoded
probabilities, and gradient-free (training decodes first — see
``core.zampling.MaskProgram``).  The default impl honors the
``REPRO_RECONSTRUCT_IMPL`` env override (mirroring
``REPRO_BATCH_MAP_THRESHOLD``); benchmarks (bench_fused ->
BENCH_reconstruct.json ``fused_mask_lifecycle`` rows) track
fused-vs-composed at the Zhou-retrieval spec point.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.qspec import (
    QSpec,
    edge_sum,
    padded_row_window,
    row_indices,
    row_values,
)
from ..core.hashrng import bernoulli_u32
from ..core.sampling import (
    mask_u32,
    quant_threshold_u24,
    sample_mask_hash,
    sample_mask_qhash,
)
from ..core.transpose_plan import (
    build_transpose_plan,
    plan_window_apply,
    resolve_bwd_path,
)
from ..core.reconstruct import (
    _insert_padding,
    _insert_padding_batched,
    _move,
    _move_batched,
    _select_valid,
    _select_valid_batched,
    _unmove,
    _unmove_batched,
    grad_z_batched_ref,
    grad_z_ref,
    reconstruct_batched_ref,
    reconstruct_ref,
)
from . import qz_reconstruct as _pk

_DEFAULT_IMPL = "ref"
_VALID_IMPLS = ("ref", "pallas")


def _interpret() -> bool:
    """Run the Pallas kernels in the interpreter only on the CPU
    backend, which has no kernel compiler; on a TPU they compile."""
    return jax.default_backend() == "cpu"


def set_default_impl(impl: str) -> None:
    """Set the process-wide default reconstruction impl."""
    global _DEFAULT_IMPL
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"unknown reconstruction impl {impl!r}; valid impls: "
            f"{', '.join(_VALID_IMPLS)}"
        )
    _DEFAULT_IMPL = impl


def _default_impl() -> str:
    """Effective default impl: the ``REPRO_RECONSTRUCT_IMPL`` env var
    overrides ``set_default_impl`` (mirroring
    ``REPRO_BATCH_MAP_THRESHOLD``) — read at trace time, so flipping it
    between jit calls of different shapes needs no code edit."""
    env = os.environ.get("REPRO_RECONSTRUCT_IMPL")
    if env is None:
        return _DEFAULT_IMPL
    if env not in _VALID_IMPLS:
        raise ValueError(
            f"REPRO_RECONSTRUCT_IMPL={env!r} is not a valid impl; "
            f"valid impls: {', '.join(_VALID_IMPLS)}"
        )
    return env


def _chunk_plan(spec: QSpec, chunks: int):
    """(rows_per_chunk, num_chunks) with rpc a multiple of 8."""
    rpc = -(-spec.m_pad // chunks) // 8 * 8 or spec.m_pad
    return rpc, -(-spec.m_pad // rpc)


def _chunk_rows_global(spec: QSpec, c, rpc):
    """Hash-RNG z-indices/values for padded rows [c*rpc, (c+1)*rpc)."""
    rp = c * rpc + jnp.arange(rpc, dtype=jnp.int32)
    rp = jnp.minimum(rp, spec.m_pad - 1)
    win = padded_row_window(spec, rp)
    idx = row_indices(spec, rp.astype(jnp.uint32))
    vals = row_values(spec, rp.astype(jnp.uint32), dtype=jnp.float32)
    return win[:, None] * spec.window + idx, vals


def _ref_chunked(spec: QSpec, z, chunks: int):
    """Row-chunked padded rows: temporaries bounded to m_pad/chunks."""
    rpc, chunks = _chunk_plan(spec, chunks)
    zf = z.astype(jnp.float32)

    def one(c):
        gidx, vals = _chunk_rows_global(spec, c, rpc)
        return edge_sum(vals * jnp.take(zf, gidx, axis=0))

    w_pad = jax.lax.map(one, jnp.arange(chunks)).reshape(-1)[: spec.m_pad]
    return _unmove(spec, _select_valid(spec, w_pad))


def _ref_chunked_batched(spec: QSpec, Z, chunks: int):
    """Batched row-chunking: the chunk's indices/values are generated
    once and contracted against all K clients, so temporaries stay at
    O(rpc·d + K·rpc) per chunk (never O(K·m·d))."""
    rpc, chunks = _chunk_plan(spec, chunks)
    zf = Z.astype(jnp.float32)

    def one(c):
        gidx, vals = _chunk_rows_global(spec, c, rpc)
        return jax.lax.map(
            lambda z: edge_sum(vals * jnp.take(z, gidx, axis=0)), zf
        )  # (K, rpc)

    w_pad = jax.lax.map(one, jnp.arange(chunks))  # (chunks, K, rpc)
    w_pad = jnp.moveaxis(w_pad, 1, 0).reshape(
        Z.shape[0], -1
    )[:, : spec.m_pad]
    return _unmove_batched(spec, _select_valid_batched(spec, w_pad))


def _chunk_live_rows(spec: QSpec, c, rpc):
    """Clamped padded-row ids for chunk ``c`` + their live mask (the
    tail chunk repeats row m_pad-1; its updates must be zeroed)."""
    loc = c * rpc + jnp.arange(rpc)
    rows = jnp.minimum(loc, spec.m_pad - 1)
    return rows, (loc < spec.m_pad).astype(jnp.float32)


def _grad_chunked(spec: QSpec, g, chunks: int):
    """Row-chunked Q^T g: bounds the (rpc, d) temporaries exactly like
    the forward ``_ref_chunked`` (the transpose scatter accumulates
    over chunks via scan)."""
    rpc, chunks = _chunk_plan(spec, chunks)
    g_pad = _insert_padding(spec, _move(spec, g.astype(jnp.float32)))

    def step(gz, c):
        gidx, vals = _chunk_rows_global(spec, c, rpc)
        rows, live = _chunk_live_rows(spec, c, rpc)
        gc = g_pad[rows] * live
        return gz.at[gidx.reshape(-1)].add(
            (vals * gc[:, None]).reshape(-1)
        ), None

    gz, _ = jax.lax.scan(step, jnp.zeros((spec.n,), jnp.float32),
                         jnp.arange(chunks))
    return gz


def _plan_chunk_tables(spec: QSpec, chunks: int, order: str):
    """The transpose plan split into window-chunks (trace constants).

    Returns (rows (nc, wpc, window·deg), vals (nc, wpc, window, deg),
    deg, wpc, pad_windows) with the window axis zero-padded to a
    multiple of wpc so a ``lax.map`` can scan it.
    """
    plan = build_transpose_plan(spec, order)
    nw = spec.num_windows
    wpc = -(-nw // chunks)
    nc = -(-nw // wpc)
    rows = plan.rows.reshape(nw, spec.window * plan.deg)
    vals = plan.vals
    pad = nc * wpc - nw
    if pad:
        rows = np.pad(rows, ((0, pad), (0, 0)))
        vals = np.pad(vals, ((0, pad), (0, 0), (0, 0)))
    return (
        jnp.asarray(rows.reshape(nc, wpc, spec.window * plan.deg)),
        jnp.asarray(vals.reshape(nc, wpc, spec.window, plan.deg)),
        plan.deg, wpc, pad,
    )


def _grad_chunked_plan(spec: QSpec, g, chunks: int, order: str):
    """Window-chunked plan gather: per-chunk GATHER TEMPORARIES are
    bounded to O(n·deg/chunks) — each window-chunk owns a contiguous
    g_pad slice, so no cross-chunk accumulation is needed.  Note the
    plan slab itself stays resident as one static constant (see the
    memory-profile note on ``_bwd_one``)."""
    rows_c, vals_c, deg, wpc, pad = _plan_chunk_tables(spec, chunks, order)
    g_pad = _insert_padding(spec, _move(spec, g.astype(jnp.float32)))
    g_pad = jnp.pad(g_pad, (0, pad * spec.rows_per_window))
    g_c = g_pad.reshape(rows_c.shape[0], wpc * spec.rows_per_window)

    def one(xs):
        r, v, gc = xs
        return plan_window_apply(spec, r, v, deg, gc, wpc)

    return jax.lax.map(one, (rows_c, vals_c, g_c)).reshape(-1)[: spec.n]


def _grad_chunked_batched_plan(spec: QSpec, G, chunks: int, order: str):
    """Batched window-chunked plan gather: one chunk's tables feed all
    K clients; per-chunk temporaries stay at O((n·deg + K·n)/chunks)."""
    rows_c, vals_c, deg, wpc, pad = _plan_chunk_tables(spec, chunks, order)
    k = G.shape[0]
    g_pad = _insert_padding_batched(
        spec, _move_batched(spec, G.astype(jnp.float32))
    )
    g_pad = jnp.pad(g_pad, ((0, 0), (0, pad * spec.rows_per_window)))
    g_c = jnp.moveaxis(
        g_pad.reshape(k, rows_c.shape[0], wpc * spec.rows_per_window), 1, 0
    )

    def one(xs):
        r, v, gc = xs  # gc (K, wpc·rpw)
        return jax.lax.map(
            lambda gk: plan_window_apply(spec, r, v, deg, gk, wpc), gc
        )

    out = jax.lax.map(one, (rows_c, vals_c, g_c))  # (nc, K, wpc·window)
    return jnp.moveaxis(out, 1, 0).reshape(k, -1)[:, : spec.n]


def _grad_chunked_batched(spec: QSpec, G, chunks: int):
    """Batched row-chunked Q^T G: one chunk-plan generation feeds all K
    per-client scatter-adds; temporaries stay at O(rpc·d + K·rpc)."""
    rpc, chunks = _chunk_plan(spec, chunks)
    g_pad = _insert_padding_batched(
        spec, _move_batched(spec, G.astype(jnp.float32))
    )

    def step(gz, c):
        gidx, vals = _chunk_rows_global(spec, c, rpc)
        rows, live = _chunk_live_rows(spec, c, rpc)
        flat = gidx.reshape(-1)

        def one(gz_k, g_k):
            gc = g_k[rows] * live
            return gz_k.at[flat].add((vals * gc[:, None]).reshape(-1))

        return jax.vmap(one)(gz, g_pad), None

    gz, _ = jax.lax.scan(
        step, jnp.zeros((G.shape[0], spec.n), jnp.float32),
        jnp.arange(chunks),
    )
    return gz


# ---------------------------------------------------------------------------
# Primal implementations (single-client and K-stacked), shared by the
# custom_vjp entry points below.
# ---------------------------------------------------------------------------

def _fwd_one(spec: QSpec, z, impl, chunks, model_size):
    if model_size is not None and spec.shard_count > 1:
        from .qz_sharded import sharded_reconstruct

        return sharded_reconstruct(spec, z, model_size)
    if impl == "pallas":
        assert spec.shard_count == 1, "pallas path is single-block layout"
        # kernel emits rows in moved (sharding-major) flat order
        return _unmove(spec, _pk.qz_reconstruct_fwd(
            spec, z, interpret=_interpret()))
    if chunks > 1:
        return _ref_chunked(spec, z, chunks)
    return reconstruct_ref(spec, z, dtype=jnp.float32)


def _bwd_one(spec: QSpec, g, impl, chunks, model_size):
    # Memory profile of the plan backward: the cached plan slab
    # (O(n·deg) rows+vals) is static read-only data, resident once per
    # (spec, order) — chunking bounds the per-chunk GATHER temporaries
    # only.  A caller that needs the scatter path's strict O(rpc·d)
    # footprint (no resident slab) gates REPRO_BWD_PLAN=scatter.
    if model_size is not None and spec.shard_count > 1:
        from .qz_sharded import sharded_grad_z

        return sharded_grad_z(spec, g.astype(jnp.float32), model_size)
    kind, order = resolve_bwd_path()
    if impl == "pallas":
        if kind == "plan":
            return _pk.qz_reconstruct_bwd_plan(spec, _move(spec, g),
                                               order=order,
                                               interpret=_interpret())
        return _pk.qz_reconstruct_bwd(spec, _move(spec, g),
                                      interpret=_interpret())
    if chunks > 1:
        if kind == "plan":
            return _grad_chunked_plan(spec, g, chunks, order)
        return _grad_chunked(spec, g, chunks)
    return grad_z_ref(spec, g)


def _fwd_many(spec: QSpec, Z, impl, chunks, model_size):
    if model_size is not None and spec.shard_count > 1:
        from .qz_sharded import sharded_reconstruct_batched

        return sharded_reconstruct_batched(spec, Z, model_size)
    if impl == "pallas":
        assert spec.shard_count == 1, "pallas path is single-block layout"
        # kernel emits rows in moved (sharding-major) flat order
        return _unmove_batched(spec, _pk.qz_reconstruct_batched_fwd(
            spec, Z, interpret=_interpret()))
    if chunks > 1:
        return _ref_chunked_batched(spec, Z, chunks)
    return reconstruct_batched_ref(spec, Z, dtype=jnp.float32)


def _bwd_many(spec: QSpec, G, impl, chunks, model_size):
    if model_size is not None and spec.shard_count > 1:
        from .qz_sharded import sharded_grad_z_batched

        return sharded_grad_z_batched(spec, G.astype(jnp.float32),
                                      model_size)
    kind, order = resolve_bwd_path()
    if impl == "pallas":
        if kind == "plan":
            return _pk.qz_reconstruct_batched_bwd_plan(
                spec, _move_batched(spec, G), order=order,
                interpret=_interpret())
        return _pk.qz_reconstruct_batched_bwd(spec, _move_batched(spec, G),
                                              interpret=_interpret())
    if chunks > 1:
        if kind == "plan":
            return _grad_chunked_batched_plan(spec, G, chunks, order)
        return _grad_chunked_batched(spec, G, chunks)
    return grad_z_batched_ref(spec, G)


# ---------------------------------------------------------------------------
# vmap-aware cores: custom_vmap rules route a batched z onto the
# natively-batched impls.  Cached so the wrapped-function identity is
# stable across traces (jit cache friendliness).
# ---------------------------------------------------------------------------

# Bounded: eviction only costs a retrace of the custom_vmap wrappers,
# never correctness, and 256 (spec, impl, chunks, model_size) combos is
# far beyond any real model's tensor count; unbounded would pin every
# spec a long-lived process ever builds.
@functools.lru_cache(maxsize=256)
def _vmap_cores(spec: QSpec, impl: str, chunks: int, model_size):
    @jax.custom_batching.custom_vmap
    def fwd_core(z):
        return _fwd_one(spec, z, impl, chunks, model_size)

    @fwd_core.def_vmap
    def _fwd_rule(axis_size, in_batched, Z):  # noqa: ARG001
        if not in_batched[0]:
            return _fwd_one(spec, Z, impl, chunks, model_size), False
        return _fwd_many(spec, Z, impl, chunks, model_size), True

    @jax.custom_batching.custom_vmap
    def bwd_core(g):
        return _bwd_one(spec, g, impl, chunks, model_size)

    @bwd_core.def_vmap
    def _bwd_rule(axis_size, in_batched, G):  # noqa: ARG001
        if not in_batched[0]:
            return _bwd_one(spec, G, impl, chunks, model_size), False
        return _bwd_many(spec, G, impl, chunks, model_size), True

    return fwd_core, bwd_core


def _make_reconstruct_op(fwd_impl, bwd_impl):
    """custom_vjp wrapper shared by the three entry points: no
    residuals, nondiff static (spec, impl, chunks, model_size)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2, 3, 4))
    def op(spec: QSpec, z, impl: str, chunks: int, model_size):
        return fwd_impl(spec, z, impl, chunks, model_size)

    def fwd(spec, z, impl, chunks, model_size):
        return op(spec, z, impl, chunks, model_size), None

    def bwd(spec, impl, chunks, model_size, _res, g):
        return (bwd_impl(spec, g, impl, chunks, model_size),)

    op.defvjp(fwd, bwd)
    return op


# vmap-aware single-client op: fwd/bwd route through the custom_vmap
# cores so a batched z lowers onto the natively-batched impls.
_reconstruct = _make_reconstruct_op(
    lambda spec, z, impl, chunks, ms: _vmap_cores(spec, impl, chunks,
                                                  ms)[0](z),
    lambda spec, g, impl, chunks, ms: _vmap_cores(spec, impl, chunks,
                                                  ms)[1](g),
)

# Naive variant WITHOUT the custom_vmap hook: under jax.vmap this
# regenerates Q per client.  Benchmark baseline + equivalence oracle.
_reconstruct_naive = _make_reconstruct_op(_fwd_one, _bwd_one)

# Explicit K-stacked entry: Z (K, n) -> W (K, *shape).
_reconstruct_b = _make_reconstruct_op(_fwd_many, _bwd_many)


def _resolve_model_size(model_size, row_sharding):
    if model_size is None and row_sharding is not None:
        shape = dict(zip(row_sharding.mesh.axis_names,
                         row_sharding.mesh.devices.shape))
        model_size = shape.get("model")
    return model_size


def reconstruct(spec: QSpec, z, *, dtype=jnp.float32, chunks: int = 1,
                impl: Optional[str] = None, model_size: Optional[int] = None,
                row_sharding=None, auto_batch: bool = True):
    """w = Q z, returned with ``spec.shape`` and ``dtype``.

    ``model_size``: size of the 'model' mesh axis — activates the
    distributed op when the spec was built with shard_count > 1.
    (``row_sharding`` kept for API compat; its mesh provides model_size.)
    ``auto_batch``: keep the custom_vmap hook that lowers
    ``jax.vmap(reconstruct)`` onto the natively-batched kernels; pass
    False to force the per-client path (benchmark baseline).
    """
    model_size = _resolve_model_size(model_size, row_sharding)
    impl = impl or _default_impl()
    fn = _reconstruct if auto_batch else _reconstruct_naive
    w = fn(spec, z.astype(jnp.float32), impl, int(chunks), model_size)
    return w.astype(dtype)


def reconstruct_batched(spec: QSpec, Z, *, dtype=jnp.float32,
                        chunks: int = 1, impl: Optional[str] = None,
                        model_size: Optional[int] = None, row_sharding=None):
    """W = Q z^(k) for K stacked clients: Z (K, n) -> (K, *spec.shape).

    Semantically identical to ``jax.vmap(reconstruct)(Z)`` (fwd and
    grad) but regenerates Q's indices/values once per row block instead
    of once per client.  Same impl dispatch as ``reconstruct``.
    """
    if Z.ndim != 2 or Z.shape[-1] != spec.n:
        raise ValueError(f"Z has shape {Z.shape}, spec expects (K, {spec.n})")
    model_size = _resolve_model_size(model_size, row_sharding)
    impl = impl or _default_impl()
    W = _reconstruct_b(spec, Z.astype(jnp.float32), impl, int(chunks),
                       model_size)
    return W.astype(dtype)


# ---------------------------------------------------------------------------
# Fused mask lifecycle: w = Q·Bern(p) and lanes = pack(Bern(p)) as one
# op each — the mask z never exists as an f32 array between ops.  The
# draw is the counter-based hash stream (core.sampling.mask_u32), so
# fused and composed (sample -> reconstruct -> pack) regenerate
# IDENTICAL bits from (spec.seed, spec.tensor_id, step, coord): the
# bit-exactness contract is exact equality, forward and gradient.
# ---------------------------------------------------------------------------

def _packed_fusable(spec: QSpec, qbits) -> bool:
    """Whole lanes per window — the packed in-block unpack needs
    ``window % (32 // qbits) == 0`` (true for every power-of-two width
    at the standard windows); other widths fall back to the unpack
    oracle below."""
    return spec.window % (32 // qbits) == 0


def _sample_one(spec: QSpec, p, step, qbits=None, qpacked=False):
    """The oracle draw for one client: z (n,) f32 in {0,1}.  With
    ``qbits`` the operand is the quantized broadcast words and the draw
    is the widened-threshold integer compare (``sample_mask_qhash``).
    With ``qpacked`` the operand is the packed uint32 lane carry
    (``comm.bitpack``); the oracle unpacks it to per-coordinate words
    first — this REF path is the one packed impl that materializes the
    (n,) word slab (it is the exactness anchor, not the fast path)."""
    if qpacked:
        from ..comm.bitpack import unpack_words

        p = unpack_words(jnp.asarray(p), spec.n, qbits)
    if qbits is not None:
        return sample_mask_qhash(p, qbits, spec.seed, spec.tensor_id, step)
    return sample_mask_hash(p, spec.seed, spec.tensor_id, step)


def _fwd_one_fused(spec: QSpec, p, step, impl, chunks, model_size,
                   qbits=None, qpacked=False):
    if model_size is not None and spec.shard_count > 1:
        # shard-local draw: each shard hashes only its own nw_loc
        # windows at GLOBAL coordinates — bit-identical to drawing the
        # replicated (n,) mask and slicing, without materializing it
        from .qz_sharded import sharded_sample_reconstruct

        if not qpacked or _packed_fusable(spec, qbits):
            return sharded_sample_reconstruct(spec, p, step, model_size,
                                              qbits=qbits, qpacked=qpacked)
    elif impl == "pallas" and (not qpacked or _packed_fusable(spec, qbits)):
        assert spec.shard_count == 1, "pallas path is single-block layout"
        return _unmove(spec, _pk.qz_sample_reconstruct_fwd(
            spec, p, step, qbits=qbits, qpacked=qpacked,
            interpret=_interpret()))
    z = _sample_one(spec, p, step, qbits, qpacked)
    if chunks > 1:
        return _ref_chunked(spec, z, chunks)
    return reconstruct_ref(spec, z, dtype=jnp.float32)


def _fwd_many_fused(spec: QSpec, P, steps, impl, chunks, model_size,
                    qbits=None, qpacked=False):
    if model_size is not None and spec.shard_count > 1:
        # shard-local batched draw (see _fwd_one_fused)
        from .qz_sharded import sharded_sample_reconstruct_batched

        if not qpacked or _packed_fusable(spec, qbits):
            return sharded_sample_reconstruct_batched(
                spec, P, steps, model_size, qbits=qbits, qpacked=qpacked)
    elif impl == "pallas" and (not qpacked or _packed_fusable(spec, qbits)):
        assert spec.shard_count == 1, "pallas path is single-block layout"
        return _unmove_batched(
            spec, _pk.qz_sample_reconstruct_batched_fwd(
                spec, P, steps, qbits=qbits, qpacked=qpacked,
                interpret=_interpret())
        )
    Z = _sample_one(spec, P, steps, qbits, qpacked)
    if chunks > 1:
        return _ref_chunked_batched(spec, Z, chunks)
    return reconstruct_batched_ref(spec, Z, dtype=jnp.float32)


@functools.lru_cache(maxsize=256)
def _fused_cores(spec: QSpec, impl: str, chunks: int, model_size):
    """vmap-aware fused forward: a batched (p, step) lowers onto the
    natively-batched fused impls (same pattern as ``_vmap_cores``; the
    backward IS ``_vmap_cores``'s bwd core — the straight-through
    cotangent does not depend on the draw)."""

    @jax.custom_batching.custom_vmap
    def fwd_core(p, step):
        return _fwd_one_fused(spec, p, step, impl, chunks, model_size)

    @fwd_core.def_vmap
    def _fwd_rule(axis_size, in_batched, P, steps):
        pb, sb = in_batched
        if not pb and not sb:
            return _fwd_one_fused(spec, P, steps, impl, chunks,
                                  model_size), False
        if not pb:
            P = jnp.broadcast_to(P, (axis_size, *P.shape))
        if not sb:
            steps = jnp.broadcast_to(steps, (axis_size,))
        return _fwd_many_fused(spec, P, steps, impl, chunks,
                               model_size), True

    return fwd_core


def _float0_like(step):
    """Cotangent for the integer step word (jax float0 convention)."""
    return np.zeros(np.shape(step), jax.dtypes.float0)


def _make_sample_reconstruct_op(fwd_impl, bwd_impl):
    """custom_vjp for the fused op: primal draws in-op; backward is the
    straight-through ``grad_p = Q^T grad_w`` — the SAME code path as
    the composed reconstruction backward, so gradients are bit-exact
    across fused/composed by construction."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3, 4, 5))
    def op(spec: QSpec, p, step, impl: str, chunks: int, model_size):
        return fwd_impl(spec, p, step, impl, chunks, model_size)

    def fwd(spec, p, step, impl, chunks, model_size):
        return op(spec, p, step, impl, chunks, model_size), step

    def bwd(spec, impl, chunks, model_size, step, g):
        return (bwd_impl(spec, g, impl, chunks, model_size),
                _float0_like(step))

    op.defvjp(fwd, bwd)
    return op


# vmap-aware fused op (the custom_vmap hook lowers vmap(local_update)
# onto the batched fused kernel) and the explicit K-stacked entry.
_sample_reconstruct = _make_sample_reconstruct_op(
    lambda spec, p, step, impl, chunks, ms: _fused_cores(
        spec, impl, chunks, ms)(p, step),
    lambda spec, g, impl, chunks, ms: _vmap_cores(spec, impl, chunks,
                                                  ms)[1](g),
)
_sample_reconstruct_b = _make_sample_reconstruct_op(_fwd_many_fused,
                                                    _bwd_many)


@functools.lru_cache(maxsize=256)
def _fused_q_cores(spec: QSpec, qbits: int, impl: str, chunks: int,
                   model_size, qpacked: bool = False):
    """vmap-aware QUANTIZED fused forward: the operand is the downlink
    codec's b-bit probability words — or, with ``qpacked``, its packed
    uint32 lane carry (``comm.bitpack``) — and the in-op draw is the
    widened-threshold integer compare.  No custom_vjp — integer wire
    words carry no cotangent (the trainable path decodes first; see
    ``core.zampling.MaskProgram``)."""

    @jax.custom_batching.custom_vmap
    def core(q, step):
        return _fwd_one_fused(spec, q, step, impl, chunks, model_size,
                              qbits, qpacked)

    @core.def_vmap
    def _rule(axis_size, in_batched, Q, steps):
        qb, sb = in_batched
        if not qb and not sb:
            return _fwd_one_fused(spec, Q, steps, impl, chunks, model_size,
                                  qbits, qpacked), False
        if not qb:
            Q = jnp.broadcast_to(Q, (axis_size, *Q.shape))
        if not sb:
            steps = jnp.broadcast_to(steps, (axis_size,))
        return _fwd_many_fused(spec, Q, steps, impl, chunks, model_size,
                               qbits, qpacked), True

    return core


def sample_reconstruct(spec: QSpec, p, step, *, dtype=jnp.float32,
                       chunks: int = 1, impl: Optional[str] = None,
                       model_size: Optional[int] = None, row_sharding=None,
                       qbits: Optional[int] = None, qpacked: bool = False):
    """w = Q·Bern(p) fused: probabilities in, weights out.

    ``step`` is the uint32 draw-counter word (``core.sampling``); the
    mask is drawn inside the op (in-block on the Pallas path) and is
    bit-identical to ``reconstruct(spec, sample_mask_hash(p, ...))``.
    Differentiable in ``p`` with the straight-through
    ``grad_p = Q^T grad_w``; chain through ``clip_probs`` for the
    paper's ``⊙ 1_{0<s<1}`` gate.  Same impl dispatch as
    ``reconstruct``.

    ``qbits``: the operand is a QUANTIZED downlink broadcast — b-bit
    probability words from the ``comm.downlink`` codec — and the in-op
    draw is the widened-threshold integer compare, bit-identical to
    the f32 path on the codec's decoded probabilities
    (``sample_mask_qhash``).  That path is gradient-free (wire words
    carry no cotangent); training decodes first.

    ``qpacked``: the operand is the packed uint32 LANE carry of the
    sub-byte codecs (``comm.downlink.PackedDown`` / ``comm.bitpack``
    layout, length ``packed_word_len(n, qbits)``): the fused impls
    stream whole lanes and unpack in-block, so the per-coordinate word
    slab never materializes (only the ref oracle unpacks up front).
    """
    model_size = _resolve_model_size(model_size, row_sharding)
    impl = impl or _default_impl()
    if qpacked and qbits is None:
        raise ValueError("qpacked requires qbits (a packed codec width)")
    if qbits is not None:
        w = _fused_q_cores(spec, int(qbits), impl, int(chunks), model_size,
                           bool(qpacked))(
            jnp.asarray(p).astype(jnp.uint32),
            jnp.asarray(step, jnp.uint32))
        return w.astype(dtype)
    w = _sample_reconstruct(spec, p.astype(jnp.float32),
                            jnp.asarray(step, jnp.uint32), impl,
                            int(chunks), model_size)
    return w.astype(dtype)


def sample_reconstruct_batched(spec: QSpec, P, steps, *, dtype=jnp.float32,
                               chunks: int = 1, impl: Optional[str] = None,
                               model_size: Optional[int] = None,
                               row_sharding=None,
                               qbits: Optional[int] = None,
                               qpacked: bool = False):
    """Fused W = Q·Bern(p^(k)) for K stacked clients: P (K, n) probs +
    steps (K,) draw words -> (K, *spec.shape).  ``qbits``/``qpacked``
    as ``sample_reconstruct``: P is the (K, n) quantized word slab, or
    the (K, n/wpl) packed lane slab."""
    exp_len = spec.n
    if qpacked:
        if qbits is None:
            raise ValueError("qpacked requires qbits (a packed codec width)")
        from ..comm.bitpack import packed_word_len

        exp_len = packed_word_len(spec.n, int(qbits))
    if P.ndim != 2 or P.shape[-1] != exp_len:
        raise ValueError(f"P has shape {P.shape}, spec expects "
                         f"(K, {exp_len})")
    model_size = _resolve_model_size(model_size, row_sharding)
    impl = impl or _default_impl()
    if qbits is not None:
        W = _fwd_many_fused(spec, jnp.asarray(P).astype(jnp.uint32),
                            jnp.asarray(steps, jnp.uint32), impl,
                            int(chunks), model_size, int(qbits),
                            bool(qpacked))
        return W.astype(dtype)
    W = _sample_reconstruct_b(spec, P.astype(jnp.float32),
                              jnp.asarray(steps, jnp.uint32), impl,
                              int(chunks), model_size)
    return W.astype(dtype)


# ---------------------------------------------------------------------------
# Fused upload draw: probabilities in, uint32 wire lanes out.
# ---------------------------------------------------------------------------

def _pack_one(spec: QSpec, p, step, impl):
    if impl == "pallas" and spec.window % 32 == 0:
        return _pk.qz_sample_pack_fwd(spec, p, step,
                                      interpret=_interpret())
    from ..comm.bitpack import pack_mask

    return pack_mask(_sample_one(spec, p, step))


def _pack_many(spec: QSpec, P, steps, impl):
    if impl == "pallas" and spec.window % 32 == 0:
        return _pk.qz_sample_pack_batched_fwd(spec, P, steps,
                                              interpret=_interpret())
    from ..comm.bitpack import pack_mask

    return pack_mask(_sample_one(spec, P, steps))


@functools.lru_cache(maxsize=256)
def _pack_cores(spec: QSpec, impl: str):
    @jax.custom_batching.custom_vmap
    def core(p, step):
        return _pack_one(spec, p, step, impl)

    @core.def_vmap
    def _rule(axis_size, in_batched, P, steps):
        pb, sb = in_batched
        if not pb and not sb:
            return _pack_one(spec, P, steps, impl), False
        if not pb:
            P = jnp.broadcast_to(P, (axis_size, *P.shape))
        if not sb:
            steps = jnp.broadcast_to(steps, (axis_size,))
        return _pack_many(spec, P, steps, impl), True

    return core


def sample_pack(spec: QSpec, p, step, *, impl: Optional[str] = None):
    """Fused end-of-round upload: lanes = pack(Bern(p)), uint32
    (ceil(n/32),).  Bit-identical to
    ``pack_mask(sample_mask_hash(p, ...))``; not differentiable (the
    upload draw carries no gradient).  The pallas impl emits whole
    lanes per z-window and needs ``spec.window % 32 == 0`` — smaller
    windows fall back to the jnp oracle (same lanes either way)."""
    impl = impl or _default_impl()
    return _pack_cores(spec, impl)(p.astype(jnp.float32),
                                   jnp.asarray(step, jnp.uint32))


def sample_pack_batched(spec: QSpec, P, steps, *,
                        impl: Optional[str] = None):
    """Fused batched upload: P (K, n) probs -> (K, ceil(n/32)) lanes."""
    if P.ndim != 2 or P.shape[-1] != spec.n:
        raise ValueError(f"P has shape {P.shape}, spec expects (K, {spec.n})")
    impl = impl or _default_impl()
    return _pack_many(spec, P.astype(jnp.float32),
                      jnp.asarray(steps, jnp.uint32), impl)


# ---------------------------------------------------------------------------
# Streaming serve ops: y = x @ W_g with W_g never materialized.  The
# decode-path contraction regenerates Q edges + mask bits per tile and
# consumes the weight values in place, so a serving node's resident
# zampled state is the ENCODED score broadcast alone (kernels.qz_decode
# has the kernel story; serve.decode drives these per leaf).  Gradient-
# free by design — serving never backprops.  Impl dispatch mirrors
# reconstruct: 'chunked' (default; lax.scan over the canonical blocks,
# bounds temporaries at O(bm·d)), 'pallas' (qz_decode kernels,
# interpret on CPU), 'ref' (reconstruct-then-matmul oracle — the ONE
# serve impl that does materialize W_g).  The REPRO_SERVE_IMPL env
# override is read at trace time.
#
# CANONICAL CONTRACTION TREE.  Floating-point summation order is part
# of the serve contract: XLA's ``jnp.dot`` reduction tree is
# context-dependent (measured on CPU: mat-mat does not bitwise equal
# its own ascending row-blocked partial sums, and at B=1 a vmapped
# row dot differs from the stacked per-row dots), so "bit-identical
# across impls" cannot lean on dot internals.  Instead every impl —
# ref, chunked, and the Pallas kernels — contracts through ONE defined
# tree: per (window, bm)-block in ascending grid order, the block's
# rows scatter into an i-aligned (NI, d_out) weight tile (each cell a
# single term, NI = bm//d_out + 2 static), and the accumulator takes
# ``y += dot(x[i_lo:i_lo+NI], tile)`` (a matvec is the one-row
# matmul).  Each weight value is itself summed over its d edge slots in
# ascending order (``core.qspec.edge_sum``) — a fused reduce's order
# depends on its context too.  Identical dot shapes, operand
# values, and add order at every step ⇒ identical bits by
# construction (up to IEEE signed zeros in all-dead tile cells),
# whatever the backend's dot does inside one tile.
# ---------------------------------------------------------------------------

_DEFAULT_SERVE_IMPL = "chunked"
_VALID_SERVE_IMPLS = ("ref", "chunked", "pallas")

# row-block size of the canonical serve tree; part of the bit-exactness
# contract (a different bm is a different summation tree)
SERVE_BM = 256


def set_default_serve_impl(impl: str) -> None:
    """Set the process-wide default serve impl."""
    global _DEFAULT_SERVE_IMPL
    if impl not in _VALID_SERVE_IMPLS:
        raise ValueError(
            f"unknown serve impl {impl!r}; valid impls: "
            f"{', '.join(_VALID_SERVE_IMPLS)}"
        )
    _DEFAULT_SERVE_IMPL = impl


def _default_serve_impl() -> str:
    """Effective serve impl: ``REPRO_SERVE_IMPL`` env override (read at
    trace time, mirroring ``REPRO_RECONSTRUCT_IMPL``), else the
    process default."""
    env = os.environ.get("REPRO_SERVE_IMPL")
    if env is None:
        return _DEFAULT_SERVE_IMPL
    if env not in _VALID_SERVE_IMPLS:
        raise ValueError(
            f"REPRO_SERVE_IMPL={env!r} is not a valid impl; "
            f"valid impls: {', '.join(_VALID_SERVE_IMPLS)}"
        )
    return env


def serve_group_dims(spec: QSpec):
    """(groups, d_in, d_out) of a spec's flat moved row space.

    The serve ops address one GROUP (stacked layer) at a time: a
    (L, d_in, d_out) leaf has L groups of contiguous rows, a 2-D leaf
    one.  Requires the single-block identity row layout (shard_count
    == 1, major_axis == 0) — the serving case; ``build_specs`` without
    a shard plan always produces it.
    """
    if spec.shard_count != 1 or spec.major_axis != 0:
        raise ValueError(
            "serve ops address the single-block identity row layout "
            f"(shard_count=1, major_axis=0); spec has shard_count="
            f"{spec.shard_count}, major_axis={spec.major_axis}"
        )
    if len(spec.shape) < 2:
        raise ValueError(f"serve ops need a >=2-D spec, got {spec.shape}")
    if len(spec.shape) == 2:
        return 1, spec.shape[0], spec.shape[1]
    groups = spec.shape[0]
    d_out = spec.shape[-1]
    d_in = 1
    for s in spec.shape[1:-1]:
        d_in *= s
    return groups, d_in, d_out


def _serve_operand(spec: QSpec, words, qbits):
    """Clip f32 scores to probabilities; pass wire words through."""
    if qbits is None:
        return jnp.clip(jnp.asarray(words).astype(jnp.float32), 0.0, 1.0)
    return jnp.asarray(words).astype(jnp.uint32)


def _serve_edge_weights(spec: QSpec, p, step, rows, qbits, qpacked=False):
    """Per-edge streamed weight values at flat rows ``rows`` (..., ).

    Regenerates the rows' Q edges, draws each edge's mask bit straight
    from the encoded score words at its global z coordinate, and
    reduces over the degree axis — the same per-row expression as the
    reconstruct kernels, so values are bit-identical to gathering the
    materialized tensor.  With ``qpacked``, ``p`` is the packed uint32
    lane carry and each edge gathers its LANE (``coords // wpl``) then
    shift/masks its word out — no per-coordinate word slab, the
    gathered temporaries stay at the edge count.
    """
    rows = jnp.asarray(rows)
    idx = row_indices(spec, rows)  # (..., d) in-window
    vals = row_values(spec, rows, dtype=jnp.float32)
    win = (rows // spec.rows_per_window).astype(jnp.int32)
    coords = win[..., None] * spec.window + idx  # global z coords
    u = mask_u32(spec.seed, spec.tensor_id, jnp.asarray(step, jnp.uint32),
                 coords)
    if qpacked:
        wpl = 32 // qbits
        lanes = jnp.take(p, (coords // wpl).reshape(-1)).reshape(
            coords.shape)
        off = (coords % wpl).astype(jnp.uint32) * jnp.uint32(qbits)
        pw = (lanes >> off) & np.uint32((1 << qbits) - 1)
    else:
        pw = jnp.take(p, coords.reshape(-1)).reshape(coords.shape)
    if qbits is None:
        bits = bernoulli_u32(u, pw)
    else:
        thr = quant_threshold_u24(pw, qbits)
        bits = ((u >> np.uint32(8)) < thr).astype(jnp.float32)
    return edge_sum(vals * bits)


def serve_tile_rows(bm: int, d_out: int) -> int:
    """NI: i-rows a bm-row flat block can straddle (static tile height).

    A contiguous run of ``bm`` flat rows starting mid-i-row touches at
    most ``ceil((bm + d_out - 1) / d_out) <= bm // d_out + 2`` distinct
    input rows of the (d_in, d_out) group.
    """
    return bm // d_out + 2


def serve_block_grid(spec: QSpec, bm: int, row_offset: int, sub: int):
    """(w0, nblocks, bpw): the canonical block enumeration for a group.

    Only the windows overlapping rows [row_offset, row_offset + sub)
    are visited — a stacked leaf costs one layer's blocks per call.
    Blocks run in ascending (window, block) order; this order is part
    of the bit-exactness contract.
    """
    bpw = max(1, -(-spec.rows_per_window // bm))
    w0 = row_offset // spec.rows_per_window
    w1 = (row_offset + sub - 1) // spec.rows_per_window
    return w0, (w1 - w0 + 1) * bpw, bpw


def _serve_contract_blocks(spec: QSpec, x, row_offset, d_in, d_out, bm,
                           w_blk_fn):
    """The canonical window-blocked contraction (see section comment).

    ``w_blk_fn(rows (bm,) int32, live (bm,) bool, t () int32) -> (bm,)
    f32`` yields block ``t``'s weight values with exact +0.0 at dead
    rows (``t`` is the canonical grid index — the hot-block cache keys
    its tiles by it).  Every serve impl and the qz_decode kernels
    replay THIS tree — identical tile shapes, operand values, and
    accumulation order — so their float sums agree bit-for-bit.
    """
    sub = d_in * d_out
    ni = serve_tile_rows(bm, d_out)
    w0, nblk, bpw = serve_block_grid(spec, bm, row_offset, sub)
    rpw = spec.rows_per_window
    xf = x.astype(jnp.float32)
    if x.ndim == 1:  # a matvec is contracted as the one-row matmul
        xf = xf[None]
    xpad = jnp.pad(xf, ((0, 0), (0, ni)))
    lane = jnp.arange(bm, dtype=jnp.int32)

    def body(y, t):
        j = t % bpw
        bstart = (w0 + t // bpw) * rpw + j * bm
        rows = bstart + lane
        live = ((rows >= row_offset) & (rows < row_offset + sub)
                & (j * bm + lane < rpw) & (rows < spec.m))
        w_blk = w_blk_fn(rows, live, t)
        i_lo = jnp.clip(bstart - row_offset, 0, sub - 1) // d_out
        pos = jnp.where(live, rows - row_offset - i_lo * d_out,
                        ni * d_out)
        tile = jnp.zeros((ni * d_out,), jnp.float32)
        tile = tile.at[pos].add(w_blk, mode="drop").reshape(ni, d_out)
        xseg = jax.lax.dynamic_slice(xpad, (0, i_lo), (xpad.shape[0], ni))
        return (y + jnp.dot(xseg, tile,
                            preferred_element_type=jnp.float32), None)

    y0 = jnp.zeros((xf.shape[0], d_out), jnp.float32)
    y, _ = jax.lax.scan(body, y0, jnp.arange(nblk, dtype=jnp.int32))
    return y[0] if x.ndim == 1 else y


def _serve_contract_chunked(spec: QSpec, p, step, x, row_offset, d_in,
                            d_out, qbits, bm, qpacked=False):
    """Streaming jnp path: each canonical block regenerates its own
    (bm,) weight values from the encoded words and is consumed by the
    tile dot in place — peak temporaries O(bm·d), no W_g anywhere."""

    def w_blk_fn(rows, live, t):
        del t
        w = _serve_edge_weights(spec, p, step, rows, qbits, qpacked)
        return jnp.where(live, w, 0.0)

    return _serve_contract_blocks(spec, x, row_offset, d_in, d_out, bm,
                                  w_blk_fn)


def _serve_contract_resident(spec: QSpec, W, x, row_offset, d_in, d_out,
                             bm):
    """Canonical blocked contraction against a MATERIALIZED leaf: the
    reconstruct-on-load serving mode's linear (a tiled dense matmul —
    the tiling pins the summation order the streaming impls replay)."""
    Wf = jnp.pad(jnp.asarray(W).reshape(-1).astype(jnp.float32),
                 (0, spec.rows_per_window + bm))

    def w_blk_fn(rows, live, t):
        del t
        return jnp.where(live, jnp.take(Wf, rows), 0.0)

    return _serve_contract_blocks(spec, x, row_offset, d_in, d_out, bm,
                                  w_blk_fn)


def _serve_contract_cached(spec: QSpec, p, step, x, row_offset, d_in,
                           d_out, qbits, bm, pool, slots, qpacked=False):
    """Hot-block-cache path: per canonical block, a ``lax.cond`` on the
    block's cache slot — a resident tile gather on a hit, the streaming
    regeneration on a miss.  Both branches produce the identical (bm,)
    values (the pool is filled by ``serve_fill_tiles``, which computes
    the miss branch's exact expression), so any slot assignment —
    empty, partial, or full — yields bit-identical output; the cache
    budget moves only the latency point.

    ``pool``: (S, bm) f32 global tile pool (S >= 1); ``slots``: (nblk,)
    int32 slot per canonical block of THIS group, -1 = uncached.  Both
    are jit arguments, so fills/evictions/invalidations never
    recompile.
    """

    def w_blk_fn(rows, live, t):
        slot = slots[t]

        def hit(_):
            return jax.lax.dynamic_index_in_dim(pool, slot, keepdims=False)

        def miss(_):
            w = _serve_edge_weights(spec, p, step, rows, qbits, qpacked)
            return jnp.where(live, w, 0.0)

        return jax.lax.cond(slot >= 0, hit, miss, None)

    return _serve_contract_blocks(spec, x, row_offset, d_in, d_out, bm,
                                  w_blk_fn)


def _serve_contract_ref(spec: QSpec, words, step, x, row_offset, d_in,
                        d_out, qbits, bm, qpacked=False):
    """Reconstruct-then-matmul oracle: materializes the full leaf, then
    contracts it through the resident (load-mode) path."""
    W = sample_reconstruct(spec, words, step, qbits=qbits, qpacked=qpacked,
                           impl="ref")
    return _serve_contract_resident(spec, W, x, row_offset, d_in, d_out,
                                    bm)


def _serve_contract(spec, words, step, x, group, qbits, impl, bm,
                    qpacked=False):
    groups, d_in, d_out = serve_group_dims(spec)
    if not 0 <= group < groups:
        raise ValueError(f"group {group} out of range [0, {groups})")
    if x.shape[-1] != d_in:
        raise ValueError(
            f"activation has trailing dim {x.shape[-1]}, spec group "
            f"expects d_in={d_in}"
        )
    row_offset = group * d_in * d_out
    if impl == "ref":
        return _serve_contract_ref(spec, words, step, x, row_offset,
                                   d_in, d_out, qbits, bm, qpacked)
    p = _serve_operand(spec, words, qbits)
    if impl == "pallas" and (not qpacked or _packed_fusable(spec, qbits)):
        from .qz_decode import qz_sample_matmul, qz_sample_matvec

        fn = qz_sample_matvec if x.ndim == 1 else qz_sample_matmul
        return fn(spec, p, step, x, row_offset=row_offset, d_in=d_in,
                  d_out=d_out, qbits=qbits, qpacked=qpacked, bm=bm,
                  interpret=_interpret())
    return _serve_contract_chunked(spec, p, step, x, row_offset, d_in,
                                   d_out, qbits, bm, qpacked)


def serve_matvec(spec: QSpec, words, step, x, *, group: int = 0,
                 qbits: Optional[int] = None, qpacked: bool = False,
                 impl: Optional[str] = None, bm: int = SERVE_BM):
    """Streamed y = x @ W_g: encoded scores + x (d_in,) -> (d_out,).

    ``words``: the serve-resident score state — f32 scores (clipped to
    probabilities in-op), the downlink codec's uint words with
    ``qbits`` set, or the packed uint32 lane carry with ``qpacked``
    (sub-byte codecs; the streamed impls gather lanes and shift/mask
    in place).  ``step`` pins the mask draw; ``group`` selects the
    stacked layer.  All impls contract through the canonical blocked
    tree (section comment), so ref/chunked/pallas agree bit-for-bit;
    'ref' IS reconstruct-then-matmul and anchors the exactness tests.
    """
    impl = impl or _default_serve_impl()
    if impl not in _VALID_SERVE_IMPLS:
        raise ValueError(
            f"unknown serve impl {impl!r}; valid impls: "
            f"{', '.join(_VALID_SERVE_IMPLS)}"
        )
    if x.ndim != 1:
        raise ValueError(f"serve_matvec takes x (d_in,), got {x.shape}")
    return _serve_contract(spec, words, step, x, int(group), qbits, impl,
                           int(bm), bool(qpacked))


def serve_matmul(spec: QSpec, words, step, X, *, group: int = 0,
                 qbits: Optional[int] = None, qpacked: bool = False,
                 impl: Optional[str] = None, bm: int = SERVE_BM):
    """Streamed Y = X @ W_g for a (B, d_in) activation batch."""
    impl = impl or _default_serve_impl()
    if impl not in _VALID_SERVE_IMPLS:
        raise ValueError(
            f"unknown serve impl {impl!r}; valid impls: "
            f"{', '.join(_VALID_SERVE_IMPLS)}"
        )
    if X.ndim != 2:
        raise ValueError(f"serve_matmul takes X (B, d_in), got {X.shape}")
    return _serve_contract(spec, words, step, X, int(group), qbits, impl,
                           int(bm), bool(qpacked))


def serve_cached_matmul(spec: QSpec, words, step, X, pool, slots, *,
                        group: int = 0, qbits: Optional[int] = None,
                        qpacked: bool = False, bm: int = SERVE_BM):
    """Streamed Y = X @ W_g with the hot-block cache in the loop.

    ``pool`` (S, bm) f32 and ``slots`` (nblk,) int32 come from
    ``serve.cache.HotBlockCache`` (slice its per-leaf slot map at
    ``group``).  Bit-identical to ``serve_matmul`` at every cache
    occupancy — a hit swaps WHERE a block's values come from, never
    what they are or how they are summed.
    """
    if X.ndim != 2:
        raise ValueError(
            f"serve_cached_matmul takes X (B, d_in), got {X.shape}"
        )
    groups, d_in, d_out = serve_group_dims(spec)
    group = int(group)
    if not 0 <= group < groups:
        raise ValueError(f"group {group} out of range [0, {groups})")
    if X.shape[-1] != d_in:
        raise ValueError(
            f"activation has trailing dim {X.shape[-1]}, spec group "
            f"expects d_in={d_in}"
        )
    p = _serve_operand(spec, words, qbits)
    return _serve_contract_cached(spec, p, step, X, group * d_in * d_out,
                                  d_in, d_out, qbits, int(bm), pool,
                                  slots, bool(qpacked))


def serve_fill_tiles(spec: QSpec, words, step, groups_idx, blocks, *,
                     qbits: Optional[int] = None, qpacked: bool = False,
                     bm: int = SERVE_BM):
    """Batched tile fill: materialize T canonical blocks' weight values.

    ``groups_idx`` / ``blocks`` are (T,) int32 (group, canonical block
    index) pairs; returns (T, bm) f32 tiles with exact +0.0 at dead
    lanes — the same values ``serve_matmul``'s miss path regenerates
    for those blocks, computed in ONE vectorized ``_serve_edge_weights``
    call (no full-leaf materialization, peak temporaries O(T·bm·d)).
    The hot-block cache's fill path: pool rows written from here are
    bit-identical to the streaming regeneration they replace.
    """
    groups, d_in, d_out = serve_group_dims(spec)
    sub = d_in * d_out
    rpw = spec.rows_per_window
    bpw = max(1, -(-rpw // bm))
    g = jnp.asarray(groups_idx, jnp.int32)
    t = jnp.asarray(blocks, jnp.int32)
    if g.shape != t.shape or g.ndim != 1:
        raise ValueError(
            f"groups_idx/blocks must be matching (T,) arrays, got "
            f"{g.shape} vs {t.shape}"
        )
    row_offset = g * sub
    w0 = row_offset // rpw
    j = t % bpw
    bstart = (w0 + t // bpw) * rpw + j * bm
    lane = jnp.arange(bm, dtype=jnp.int32)
    rows = bstart[:, None] + lane[None, :]
    live = ((rows >= row_offset[:, None])
            & (rows < row_offset[:, None] + sub)
            & ((j * bm)[:, None] + lane[None, :] < rpw)
            & (rows < spec.m))
    p = _serve_operand(spec, words, qbits)
    w = _serve_edge_weights(spec, p, step, rows, qbits, bool(qpacked))
    return jnp.where(live, w, 0.0)


def _serve_resident_dims(spec: QSpec, group: int, x):
    groups, d_in, d_out = serve_group_dims(spec)
    if not 0 <= group < groups:
        raise ValueError(f"group {group} out of range [0, {groups})")
    if x.shape[-1] != d_in:
        raise ValueError(
            f"activation has trailing dim {x.shape[-1]}, spec group "
            f"expects d_in={d_in}"
        )
    return group * d_in * d_out, d_in, d_out


def serve_resident_matvec(spec: QSpec, W, x, *, group: int = 0,
                          bm: int = SERVE_BM):
    """y = x @ W_g against a materialized leaf, canonical tree.

    The reconstruct-on-load serving mode's linear: ``W`` is the full
    reconstructed leaf (spec.shape).  Contracting through the same
    blocked tree as the streamed impls is what makes load-mode serving
    bit-identical to streaming-mode serving — the modes differ only in
    WHERE the block's weight values come from (a resident tensor vs an
    in-block regeneration), never in how they are summed.
    """
    if x.ndim != 1:
        raise ValueError(
            f"serve_resident_matvec takes x (d_in,), got {x.shape}"
        )
    row_offset, d_in, d_out = _serve_resident_dims(spec, int(group), x)
    return _serve_contract_resident(spec, W, x, row_offset, d_in, d_out,
                                    int(bm))


def serve_resident_matmul(spec: QSpec, W, X, *, group: int = 0,
                          bm: int = SERVE_BM):
    """Y = X @ W_g against a materialized leaf for (B, d_in) batches."""
    if X.ndim != 2:
        raise ValueError(
            f"serve_resident_matmul takes X (B, d_in), got {X.shape}"
        )
    row_offset, d_in, d_out = _serve_resident_dims(spec, int(group), X)
    return _serve_contract_resident(spec, W, X, row_offset, d_in, d_out,
                                    int(bm))


def serve_embed_rows(spec: QSpec, words, step, tokens, *,
                     qbits: Optional[int] = None, qpacked: bool = False):
    """Streamed embedding-row gather: tokens (...) int -> (..., d_out).

    Row t of a 2-D (vocab, d_model) leaf is the contiguous flat-row
    run [t*d_model, (t+1)*d_model); the per-edge draw regenerates just
    those rows — bit-identical to ``jnp.take`` on the materialized
    table, at O(B·d_model·d) hashes per token batch.  Pure jnp on
    every impl (a gather has no contraction to fuse into).
    """
    groups, d_in, d_out = serve_group_dims(spec)
    if groups != 1:
        raise ValueError(
            f"serve_embed_rows addresses 2-D table leaves; spec shape "
            f"{spec.shape} has {groups} stacked groups"
        )
    p = _serve_operand(spec, words, qbits)
    tokens = jnp.asarray(tokens, jnp.int32)
    rows = tokens[..., None] * d_out + jnp.arange(d_out, dtype=jnp.int32)
    return _serve_edge_weights(spec, p, step, rows, qbits, bool(qpacked))
