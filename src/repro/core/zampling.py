"""Zampling as a first-class reparametrization over model param trees.

Given any model's parameter template (a pytree of arrays or
ShapeDtypeStructs), Zampling replaces each large leaf with a QSpec and a
trainable score vector ``s`` (n floats, n = m/compression).  The
trainable state of the whole model is the collection of score vectors
plus the small dense leaves (norm scales, biases, ...) that are not
worth reparametrizing — the paper applies Q to the weight matrices.

Pipeline per step (training-by-sampling):
    p = clip(s)                         # f(x), §1.3
    z ~ Bern(p)  (straight-through)     # fresh every step
    w = Q z      (materialization-free) # kernels/ops.py dispatch
    loss = model.apply(w, batch); grad flows w -> z -> s

The mask lifecycle (which mode, whether the draw is fused into the
reconstruction/pack kernels, and whether the upload leaves as uint32
wire lanes) is configured ONCE per use as a ``MaskProgram`` — the
single implementation behind ``sample_masks``/``sample_weights`` here
and ``local_update`` in ``core.federated``.  Draws are keyed by the
counter-based hash RNG (``core.sampling.mask_u32``), never
``jax.random``, so the jnp oracle and the Pallas kernels regenerate
identical bits from ``(seed, tensor_id, step, coord)``.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..tracing import FED_MODEL
from .qspec import QSpec, make_qspec
from .sampling import (
    as_word,
    clip_probs,
    discretize_mask,
    init_scores,
    sample_mask_hash,
    sample_mask_qhash,
    sample_mask_st_hash,
)

PathLeaf = Tuple[str, Any]

# Valid mask lifecycles; shared by MaskProgram and FederatedConfig.
MASK_MODES = ("sample", "continuous", "discretize")


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


@dataclass(frozen=True)
class ZamplingConfig:
    """Reparametrization hyper-parameters (paper notation in brackets)."""

    compression: float = 32.0  # m/n
    d: int = 8  # non-zeros per row of Q
    window: int = 512  # TPU adaptation: z-window size
    seed: int = 0  # shared server/client seed for Q
    min_size: int = 1024  # leaves smaller than this stay dense
    mode: str = "sample"  # sample | continuous | discretize
    chunks: int = 1  # reconstruction row-chunking (perf knob)
    shard_align: int = 1  # round num_windows to this (mesh model size)


@dataclass(frozen=True)
class ZamplingSpecs:
    """Static spec set for one model. Not a pytree — closure constant."""

    specs: Dict[str, QSpec]
    dense_paths: Tuple[str, ...]
    template: Any  # pytree of ShapeDtypeStruct (full model params)
    config: ZamplingConfig

    @property
    def m_total(self) -> int:
        return sum(s.m for s in self.specs.values())

    @property
    def n_total(self) -> int:
        return sum(s.n for s in self.specs.values())

    @property
    def dense_total(self) -> int:
        leaves = {p: l for p, l in _flatten(self.template)}
        return sum(int(jnp.size(leaves[p])) if hasattr(leaves[p], "size") else 0
                   for p in self.dense_paths)

    @property
    def compression(self) -> float:
        return self.m_total / max(self.n_total, 1)

    def comm_bits_per_round(self, packed: bool = True,
                            downlink: str = "f32") -> Dict[str, int]:
        """Analytic communication accounting (paper Table 1).

        ``client_up``/``server_down`` are the paper's IDEALIZED figures
        (n mask bits up, n score coordinates down at the configured
        downlink codec's b bits each) and deliberately ignore two
        real-wire costs: (a) masks travel as uint32 lanes, so each
        tensor pays up to 31 bits of lane padding, and (b) the dense
        (non-reparametrized) leaves are trained and averaged too, f32
        both ways.  The ``*_wire`` keys are the EXACT protocol figures
        including both — they match ``comm.metering.round_wire_report``
        bit-for-byte (pinned in tests/test_fused.py and
        tests/test_downlink.py): ``client_up_wire`` == 8x the metered
        ``uplink_bytes_per_client`` for the packed
        (``psum_u32``/``allgather_packed``) resp. ``mean_f32``
        transports, and ``server_down_wire`` == 8x the metered
        ``downlink_bytes_per_client`` for the configured codec.
        """
        from ..comm.bitpack import packed_len  # comm sits above core
        from ..comm.downlink import get_codec
        from ..comm.metering import score_downlink_bytes

        codec = get_codec(downlink)
        n, m = self.n_total, self.m_total
        dense_bits = 32 * self.dense_total
        lane_bits = sum(32 * packed_len(s.n) for s in self.specs.values())
        mask_up_wire = lane_bits if packed else 32 * n
        # the SAME per-tensor byte ceiling the metering applies, so the
        # pinned server_down_wire == 8 x metered-bytes equality cannot
        # drift between the two implementations
        down_wire = sum(
            8 * score_downlink_bytes(codec, s.n)
            for s in self.specs.values()
        )
        return {
            "naive_client_up": 32 * m,
            "client_up": n if packed else 8 * n,
            "server_down": codec.bits * n,
            "naive_server_down": 32 * m,
            "client_up_wire": mask_up_wire + dense_bits,
            "server_down_wire": down_wire + dense_bits,
        }


def _flatten(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_path_str(p), l) for p, l in flat]


def default_fan_in(path: str, shape) -> int:
    """Fan-in of the target neuron for He-style sigma (Lemma 2.1).

    Convention: weights are stored (..., in, out) — fan-in is the
    product of all-but-last dims.  Embedding tables ('embed' in path)
    use the model dim instead (their rows are looked up, not summed).
    """
    if len(shape) < 2:
        return max(int(shape[0]) if shape else 1, 1)
    if "embed" in path.lower():
        return int(shape[-1])
    fan = 1
    for s in shape[:-1]:
        fan *= int(s)
    return max(fan, 1)


def build_specs(
    template,
    config: ZamplingConfig,
    fan_in_fn: Callable[[str, tuple], int] = default_fan_in,
    shard_plan_fn: Optional[Callable[[str, tuple], Optional[int]]] = None,
) -> ZamplingSpecs:
    """Assign a QSpec to every large leaf of the param template.

    ``shard_plan_fn(path, shape) -> axis | None``: which tensor axis the
    runtime shards over 'model' — reconstruction then uses the
    sharding-major layout (shard_count = config.shard_align) so weights
    come out pre-sharded (see QSpec docstring).
    """
    template = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l)), template
    )
    specs: Dict[str, QSpec] = {}
    dense = []
    for tid, (path, leaf) in enumerate(_flatten(template)):
        m = 1
        for s in leaf.shape:
            m *= int(s)
        if len(leaf.shape) >= 2 and m >= config.min_size:
            axis = shard_plan_fn(path, leaf.shape) if shard_plan_fn else None
            specs[path] = make_qspec(
                tid,
                leaf.shape,
                fan_in_fn(path, leaf.shape),
                compression=config.compression,
                d=config.d,
                window=config.window,
                seed=config.seed,
                align=config.shard_align,
                major_axis=0 if axis is None else axis,
                shard_count=1 if axis is None else config.shard_align,
            )
        else:
            dense.append(path)
    return ZamplingSpecs(
        specs=specs, dense_paths=tuple(dense), template=template, config=config
    )


# ---------------------------------------------------------------------------
# Trainable state
# ---------------------------------------------------------------------------

def init_state(key, zspecs: ZamplingSpecs, dense_init=None) -> Dict[str, Any]:
    """{'scores': {path: f32[n]}, 'dense': {path: array}}.

    ``dense_init``: optional pytree of actual params to take dense leaves
    from (e.g. a real model init); falls back to ones/zeros heuristics.
    """
    scores = {}
    for path, spec in zspecs.specs.items():
        key, sub = jax.random.split(key)
        scores[path] = init_scores(sub, spec.n)
    dense = {}
    dense_leaves = dict(_flatten(dense_init)) if dense_init is not None else {}
    tmpl = dict(_flatten(zspecs.template))
    for path in zspecs.dense_paths:
        if path in dense_leaves:
            dense[path] = dense_leaves[path]
        else:
            leaf = tmpl[path]
            init = jnp.ones if ("scale" in path or "norm" in path.lower()) else jnp.zeros
            dense[path] = init(leaf.shape, leaf.dtype)
    return {"scores": scores, "dense": dense}


def state_spec(zspecs: ZamplingSpecs):
    """ShapeDtypeStructs of the trainable state (for dry-run lowering)."""
    scores = {
        p: jax.ShapeDtypeStruct((s.n,), jnp.float32)
        for p, s in zspecs.specs.items()
    }
    tmpl = dict(_flatten(zspecs.template))
    dense = {
        p: jax.ShapeDtypeStruct(tmpl[p].shape, tmpl[p].dtype)
        for p in zspecs.dense_paths
    }
    return {"scores": scores, "dense": dense}


# ---------------------------------------------------------------------------
# The mask program: one abstraction for the whole mask lifecycle
# (mode x fused/composed x packed-ness).  core.federated and the public
# sample_masks/sample_weights below all route through it — there is ONE
# implementation of the mode dispatch and ONE draw keying scheme
# (core.sampling.mask_u32: (spec.seed, spec.tensor_id, step, coord)).
# ---------------------------------------------------------------------------

def validate_mask_mode(mode: str) -> str:
    if mode not in MASK_MODES:
        raise ValueError(
            f"unknown mask mode {mode!r}; valid modes: "
            f"{', '.join(MASK_MODES)}"
        )
    return mode


@dataclass(frozen=True)
class MaskProgram:
    """One configured mask lifecycle over a spec set.

    ``fused=True`` routes mode='sample' through the fused kernels
    (``kernels.ops.sample_reconstruct`` / ``sample_pack``): scores in,
    weights / wire lanes out, the mask a transient in-kernel value.
    ``fused=False`` is the composed oracle — explicit straight-through
    draw, then reconstruct/pack — bit-identical to fused (exact
    equality, forward and gradient) by the shared hash-RNG keying.
    ``packed`` selects the upload representation: uint32 wire lanes
    (what the packed transports move) vs the f32 {0,1} mask.
    ``downlink`` names the registered ``comm.downlink`` codec of the
    server broadcast: the ``*_from_wire`` methods below consume the
    ENCODED score pytree directly — for the quantized codecs the
    sample-mode draw is the widened-threshold integer compare
    (``core.sampling.sample_mask_qhash``; in the fused kernels via
    ``ops.sample_reconstruct(..., qbits=b)``), so no dequantized f32
    score slab exists on the draw path.  ``step`` everywhere below is
    the uint32 draw-counter word; callers derive it from their PRNG
    key + round/client/local-step counters
    (``core.sampling.key_word``/``fold_word``).
    """

    zspecs: ZamplingSpecs
    mode: str = "sample"
    fused: bool = True
    packed: bool = False
    downlink: str = "f32"  # registered comm.downlink codec name
    impl: Optional[str] = None  # kernels impl override (None = default)

    def __post_init__(self):
        validate_mask_mode(self.mode)

    @property
    def codec(self):
        """The resolved downlink codec (raises on unknown names)."""
        from ..comm.downlink import get_codec  # comm sits above core

        return get_codec(self.downlink)

    def _wire_words(self, wire_scores, path: str):
        """Validate + fetch one tensor's encoded broadcast leaf (b-bit
        words, or uint32 LANES for the packed codecs — lane count
        validated against the spec, since every packed codec shares the
        uint32 carrier and dtype alone cannot tell them apart)."""
        codec = self.codec
        q = wire_scores[path]
        if jnp.asarray(q).dtype != jnp.dtype(codec.wire_dtype):
            raise ValueError(
                f"score leaf {path!r} has dtype {jnp.asarray(q).dtype}, "
                f"but downlink codec {codec.name!r} carries "
                f"{jnp.dtype(codec.wire_dtype).name}; encode the state "
                f"first (core.federated.encode_state)"
            )
        if codec.packed:
            spec = self.zspecs.specs[path]
            want = codec.wire_len(spec.n)
            got = jnp.shape(q)[-1]
            if got != want:
                raise ValueError(
                    f"score leaf {path!r} has {got} uint32 lanes but "
                    f"codec {codec.name!r} packs n={spec.n} words into "
                    f"{want} lanes — wrong packed codec for this carry?"
                )
        return q

    def decode_scores(self, wire_scores) -> Dict[str, Any]:
        """Encoded broadcast -> the client's f32 trainable score copy
        (identity for the ``f32`` oracle codec — same arrays, so the
        f32 path stays bit-identical to the pre-codec protocol)."""
        codec = self.codec
        if not codec.quantized:
            return dict(wire_scores)
        return {
            path: codec.decode(spec, self._wire_words(wire_scores, path))
            for path, spec in self.zspecs.specs.items()
        }

    # -- composed masks ------------------------------------------------
    def mask(self, p, spec: QSpec, step):
        """One tensor's mask from CLIPPED probabilities ``p`` (the mode
        dispatch formerly duplicated across zampling._mask and
        federated._client_masks)."""
        if self.mode == "sample":
            return sample_mask_st_hash(p, spec.seed, spec.tensor_id, step)
        if self.mode == "continuous":
            return p
        return discretize_mask(p)

    def masks(self, scores, step) -> Dict[str, Any]:
        """{path: mask}, one fresh draw per tensor at draw word ``step``."""
        return {
            path: self.mask(clip_probs(scores[path]), spec, step)
            for path, spec in self.zspecs.specs.items()
        }

    # -- weights -------------------------------------------------------
    def weights(self, scores, dense, step,
                constraints: Optional[Dict[str, Any]] = None,
                row_sharding=None):
        """Full param pytree for one forward pass at draw word ``step``."""
        if not (self.fused and self.mode == "sample"):
            return weights_from_masks(
                self.zspecs, self.masks(scores, step), {"dense": dense},
                constraints=constraints, row_sharding=row_sharding,
                impl=self.impl,
            )
        from ..kernels import ops  # late import: kernels sit above core

        tmpl = dict(_flatten(self.zspecs.template))
        leaves = {}
        for path, spec in self.zspecs.specs.items():
            # the operand's clip is the model's; no scope may enclose
            # the kernel call (repro.tracing)
            with jax.named_scope(FED_MODEL):
                p = clip_probs(scores[path])
            w = ops.sample_reconstruct(
                spec, p, step,
                dtype=tmpl[path].dtype, chunks=self.zspecs.config.chunks,
                impl=self.impl, row_sharding=row_sharding,
            )
            if constraints is not None and path in constraints:
                w = jax.lax.with_sharding_constraint(w, constraints[path])
            leaves[path] = w
        for path in self.zspecs.dense_paths:
            leaves[path] = dense[path]
        return unflatten_like(self.zspecs.template, leaves)

    # -- the wire draw -------------------------------------------------
    def upload(self, scores, step) -> Dict[str, Any]:
        """The end-of-round upload per tensor: fresh (gradient-free)
        Bernoulli bits at draw word ``step`` — as uint32 wire lanes
        when ``packed`` (what the packed transports move natively),
        else as the f32 {0,1} mask.  Discretize mode uploads rounded
        bits (binary, so packable too); continuous mode uploads
        probabilities (f32 only — ``mean_f32`` wire)."""
        from ..kernels import ops

        out = {}
        for path, spec in self.zspecs.specs.items():
            p = clip_probs(scores[path])
            if self.mode == "continuous":
                out[path] = p
            elif self.mode == "discretize":
                if self.packed:
                    from ..comm.bitpack import pack_mask

                    out[path] = pack_mask(discretize_mask(p))
                else:
                    out[path] = discretize_mask(p)
            elif self.packed and self.fused:
                out[path] = ops.sample_pack(spec, p, step, impl=self.impl)
            elif self.packed:
                from ..comm.bitpack import pack_mask

                out[path] = pack_mask(
                    sample_mask_hash(p, spec.seed, spec.tensor_id, step)
                )
            else:
                out[path] = sample_mask_hash(p, spec.seed, spec.tensor_id,
                                             step)
        return out

    # -- drawing straight from the encoded broadcast -------------------
    def mask_from_wire(self, q, spec: QSpec, step):
        """One tensor's mask from its ENCODED broadcast words.  Sample
        mode is the widened-threshold integer compare — bit-identical
        to ``self.mask(codec.decode(q), ...)`` without materializing
        the decoded f32 probabilities (discretize compares the
        threshold against 2^23, i.e. p_hat >= 0.5)."""
        codec = self.codec
        if not codec.quantized:
            return self.mask(clip_probs(q), spec, step)
        if self.mode == "sample":
            return sample_mask_qhash(codec.wire_words(spec, q),
                                     codec.bits, spec.seed,
                                     spec.tensor_id, step)
        if self.mode == "continuous":
            return codec.decode(spec, q)
        thr = codec.threshold_u24(codec.wire_words(spec, q))
        return (thr >= jnp.uint32(1 << 23)).astype(jnp.float32)

    def masks_from_wire(self, wire_scores, step) -> Dict[str, Any]:
        """{path: mask} drawn directly from the encoded broadcast."""
        return {
            path: self.mask_from_wire(self._wire_words(wire_scores, path),
                                      spec, step)
            for path, spec in self.zspecs.specs.items()
        }

    def weights_from_wire(self, wire_scores, dense, step,
                          constraints: Optional[Dict[str, Any]] = None,
                          row_sharding=None):
        """Full param pytree sampled straight from the encoded
        broadcast — the serving/eval path for a quantized downlink
        state.  Gradient-free (the broadcast carries no cotangent; the
        trainable path decodes first via ``decode_scores``).  Fused
        sample mode hands the quantized words to the kernels
        (``ops.sample_reconstruct(..., qbits=b)``: threshold compare
        in-block), bit-identical to the composed
        ``masks_from_wire`` -> ``weights_from_masks`` oracle."""
        codec = self.codec
        if not codec.quantized:
            return self.weights(wire_scores, dense, step,
                                constraints=constraints,
                                row_sharding=row_sharding)
        if not (self.fused and self.mode == "sample"):
            return weights_from_masks(
                self.zspecs, self.masks_from_wire(wire_scores, step),
                {"dense": dense}, constraints=constraints,
                row_sharding=row_sharding, impl=self.impl,
            )
        from ..kernels import ops  # late import: kernels sit above core

        tmpl = dict(_flatten(self.zspecs.template))
        leaves = {}
        for path, spec in self.zspecs.specs.items():
            w = ops.sample_reconstruct(
                spec, self._wire_words(wire_scores, path), step,
                qbits=codec.bits, qpacked=codec.packed,
                dtype=tmpl[path].dtype,
                chunks=self.zspecs.config.chunks, impl=self.impl,
                row_sharding=row_sharding,
            )
            if constraints is not None and path in constraints:
                w = jax.lax.with_sharding_constraint(w, constraints[path])
            leaves[path] = w
        for path in self.zspecs.dense_paths:
            leaves[path] = dense[path]
        return unflatten_like(self.zspecs.template, leaves)


def infer_downlink(scores) -> str:
    """Infer the broadcast codec of a score pytree from its leaf dtypes
    — floating leaves are plain/``f32`` scores, uint leaves name the
    quantized codec that carries them.  VALIDATED FALLBACK only: every
    packed codec's wire dtype is uint32, so dtype sniffing RAISES on a
    packed carry (``comm.downlink.codec_for_dtype``) — route those by
    explicit tag (``carried=`` on ``sample_weights``/``sample_masks``/
    ``evaluate``/``make_serve_state``, or the checkpoint's
    ``meta['downlink']``)."""
    from ..comm.downlink import codec_for_dtype  # comm sits above core

    dtypes = {jnp.asarray(v).dtype for v in scores.values()}
    names = {codec_for_dtype(dt).name for dt in dtypes}
    if len(names) > 1:
        raise ValueError(
            f"score leaves mix downlink representations {sorted(names)}"
        )
    return names.pop() if names else "f32"


def validate_carried(zspecs: ZamplingSpecs, scores, carried: str) -> str:
    """Validate an EXPLICIT codec tag against the score leaves and
    return the canonical codec name — the tag-routing counterpart of
    ``infer_downlink`` (which cannot distinguish the uint32-laned
    packed codecs).  Checks dtype for every codec and the per-tensor
    lane count for the packed family, so a wrong tag fails loudly
    instead of mis-decoding the carry."""
    from ..comm.downlink import get_codec  # comm sits above core

    codec = get_codec(carried)
    for path, spec in zspecs.specs.items():
        leaf = jnp.asarray(scores[path])
        if codec.quantized:
            ok = (leaf.dtype == jnp.dtype(codec.wire_dtype)
                  and leaf.shape[-1] == codec.wire_len(spec.n))
        else:
            ok = jnp.issubdtype(leaf.dtype, jnp.floating)
        if not ok:
            raise ValueError(
                f"score leaf {path!r} (dtype {leaf.dtype}, trailing dim "
                f"{leaf.shape[-1]}) cannot carry the tagged codec "
                f"{codec.name!r} (wire dtype "
                f"{jnp.dtype(codec.wire_dtype).name}, wire length "
                f"{codec.wire_len(spec.n)} for n={spec.n})"
            )
    return codec.name


def resolve_carried(zspecs: ZamplingSpecs, scores,
                    carried: Optional[str] = None) -> str:
    """The ONE carried-representation resolver: an explicit tag is
    validated (``validate_carried``); without one, dtype sniffing
    (``infer_downlink``) is the fallback and raises on ambiguity."""
    if carried is not None:
        return validate_carried(zspecs, scores, carried)
    return infer_downlink(scores)


def sample_masks(zspecs: ZamplingSpecs, state, key,
                 mode: Optional[str] = None,
                 carried: Optional[str] = None):
    """{path: z} straight-through masks, one fresh draw per tensor.

    ``key``: a PRNG key or uint32 draw word (``core.sampling.as_word``).
    ``carried`` names the codec of an encoded score state explicitly
    (required for the packed uint32-lane codecs); without it the
    representation is inferred from leaf dtypes, which raises on
    ambiguity.  Quantized carries draw through the widened-threshold
    integer compare.
    """
    downlink = resolve_carried(zspecs, state["scores"], carried)
    program = MaskProgram(zspecs, mode=mode or zspecs.config.mode,
                          fused=False, downlink=downlink)
    if program.codec.quantized:
        return program.masks_from_wire(state["scores"], as_word(key))
    return program.masks(state["scores"], as_word(key))


def weights_from_masks(zspecs: ZamplingSpecs, masks, state,
                       constraints: Optional[Dict[str, Any]] = None,
                       row_sharding=None, impl: Optional[str] = None):
    """Reconstruct the full model param tree from masks + dense leaves.

    ``constraints``: optional {path: NamedSharding} applied to each
    reconstructed tensor (GSPMD anchor for the distributed runtime).
    ``row_sharding``: optional NamedSharding for the (num_windows,
    rows_per_window) reconstruction row space (shards the O(m d)
    temporaries over 'model').
    """
    from ..kernels import ops  # late import: kernels layer sits above core

    tmpl = dict(_flatten(zspecs.template))
    leaves = {}
    for path, spec in zspecs.specs.items():
        w = ops.reconstruct(
            spec, masks[path], dtype=tmpl[path].dtype,
            chunks=zspecs.config.chunks, row_sharding=row_sharding,
            impl=impl,
        )
        if constraints is not None and path in constraints:
            w = jax.lax.with_sharding_constraint(w, constraints[path])
        leaves[path] = w
    for path in zspecs.dense_paths:
        leaves[path] = state["dense"][path]
    return unflatten_like(zspecs.template, leaves)


def sample_weights(zspecs: ZamplingSpecs, state, key,
                   mode: Optional[str] = None,
                   constraints: Optional[Dict[str, Any]] = None,
                   row_sharding=None, fused: bool = True,
                   downlink: Optional[str] = None,
                   carried: Optional[str] = None):
    """One fresh sampled network: params pytree matching the template.

    Routes through ``MaskProgram``: with ``fused`` (default) the
    sample-mode draw happens inside the fused reconstruction kernel;
    ``fused=False`` is the composed bit-exact oracle.  ``carried``
    names the codec of an encoded score state EXPLICITLY (validated
    against the leaves; required for the packed uint32-lane codecs,
    whose dtype is ambiguous); without it the representation is
    inferred from leaf dtypes, which raises on ambiguity —
    ``train.local.evaluate(..., carried=tag)`` threads the tag through.
    An explicit ``downlink`` must agree with the carried representation
    (treating wire words as f32 scores would silently clip them all to
    p=1).
    """
    from ..comm.downlink import get_codec  # comm sits above core

    resolved = resolve_carried(zspecs, state["scores"], carried)
    if downlink is not None and get_codec(downlink).name != resolved:
        raise ValueError(
            f"downlink={downlink!r} does not match the state's score "
            f"representation ({resolved!r})"
        )
    program = MaskProgram(zspecs, mode=mode or zspecs.config.mode,
                          fused=fused, downlink=resolved)
    if program.codec.quantized:
        return program.weights_from_wire(
            state["scores"], state["dense"], as_word(key),
            constraints=constraints, row_sharding=row_sharding)
    return program.weights(state["scores"], state["dense"], as_word(key),
                           constraints=constraints,
                           row_sharding=row_sharding)


def unflatten_like(template, leaves: Dict[str, Any]):
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    ordered = [leaves[_path_str(p)] for p, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, ordered)
