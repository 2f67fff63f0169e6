"""FEDERATED ZAMPLING (paper §1.3, federated version).

One round:
  1. server "broadcasts" p(t)           -> replication across clients
  2. client k: s = p(t); E local steps of SGD/Adam on the scores with a
     FRESH mask sample every forward pass (training-by-sampling)
  3. client k: p_new = f(s); z_new ~ Bern(p_new)  (n BITS on the wire)
  4. server: p(t+1) = mean_k z_new^(k)

Fused mask lifecycle (this module's hot path): the mask ``z`` is n
bits, and with ``FederatedConfig.mask_path='fused'`` (default) it
NEVER exists as an f32 array between ops.  Every draw is keyed by the
counter-based hash RNG (``core.sampling.mask_u32``: words
``(spec.seed, spec.tensor_id, step, coord)``), where ``step`` is a
uint32 draw word derived from (round key, round_index, client index,
local step) — integer counters threaded through the scans, NOT
pre-split PRNG keys.  Step 2's per-forward draw happens inside the
fused reconstruction kernel (``kernels.ops.sample_reconstruct``:
scores in, weights out, straight-through ``grad_s = Q^T grad_w ⊙
1_{0<s<1}`` via its custom_vjp); step 3's upload draw happens inside
the fused pack kernel (``kernels.ops.sample_pack``: scores in, uint32
wire lanes out).  ``mask_path='composed'`` is the bit-exact oracle —
explicit draw, then reconstruct/pack — equal to fused to EXACT
equality, forward and gradient (tests/test_fused.py).  All mode
dispatch lives in ONE place, ``core.zampling.MaskProgram`` (mode x
fused x packed-ness).

Step 3/4 — what actually crosses the network upstream — is delegated
to the wire-format transport layer (``repro.comm``): ``FederatedConfig
.aggregate`` names a registered ``comm.protocol.Transport`` strategy
(``mean_f32`` f32 baseline, ``psum_u32`` integer popcount psum of
bitpacked lanes, ``allgather_packed`` raw-lane all-gather; ``mean`` is
a backwards-compatible alias of ``mean_f32``).  Packed transports
receive the clients' uint32 lanes NATIVELY (``aggregate_*_packed``) —
there is no post-hoc jnp pack of an f32 mask slab.  All strategies are
bit-exact against each other; they differ only in wire bytes, which
``comm.metering`` reports exactly in every round's metrics
(``uplink_bytes_per_client`` etc.).  Continuous-mode rounds upload
probabilities, not bits, and always use ``mean_f32``.

Step 1 — the DOWNLINK — is symmetric since the codec subsystem
(``comm.downlink``): ``FederatedConfig.downlink`` names a registered
``DownlinkCodec`` and the ENCODED scores ARE the round's carried
state.  ``federated_round`` / ``sharded_client_update`` take
``state['scores']`` in the codec's wire representation, the client
decodes only its own trainable copy (``MaskProgram.decode_scores``),
and after aggregation the server re-encodes ``p(t+1)`` with the
shared dither word ``fold_word(key_word(key), round_index)`` — every
shard regenerates the identical dither from the replicated key, so
the encoded broadcast is bit-identical across the vmap and shard_map
paths with zero extra bits.  ``downlink='f32'`` (default) is the
identity oracle: those rounds are bit-identical to the pre-codec
protocol.  Quantized codecs (``u16``/``u8``) cut the dominant
``server_down_wire`` term 2x/4x; mask draws made straight from the
broadcast (eval/serving, ``MaskProgram.*_from_wire``) use the
widened-threshold integer compare and never materialize a dequantized
f32 score slab.  ``encode_state`` converts an f32 init state into the
configured wire representation before the first round.

PARTIAL PARTICIPATION (the fault-tolerant round, ``repro.fault``):
the full-participation round above is the special case every client
shows up.  Passing ``client_ids`` / ``weights`` / ``faults`` to either
driver switches the server update to the weighted partial form

    p(t+1) = sum_k w_k·b_k·z^(k) / sum_k w_k·b_k,

where ``w_k`` is client k's sample-count weight (``fault.population
.ClientPopulation``, e.g. Dirichlet split sizes) and ``b_k ∈ {0,1}``
is its REALIZED participation bit: 0 if the client dropped, straggled
past the round cutoff, or failed the server's upload validation
(``fault.validate`` popcount checksums detect the lane corruption
``FaultPlan`` injects).  Both factors enter the popcount reduction as
exact uint32 multiplies (``comm.protocol`` ``*_weighted``), so the
mean over the survivors is EXACT — the same integers in every wire
representation — and the realized denominator replaces the configured
K (the divide-by-K mean is silently wrong the moment anyone drops).
A round whose surviving cohort falls below ``FederatedConfig
.min_clients`` (or whose realized weight is zero) is SKIPPED: the
carried state — scores in the downlink codec's wire words, dense
leaves — passes through unchanged and the metrics flag
``round_skipped=1``; averaging two survivors of a hundred would move
p(t) by sampling noise, not signal.  With all clients participating
at weight 1 every multiply is an identity and the weighted round is
bit-identical to the plain protocol (tests/test_faults.py); with no
participation arguments at all the plain code path runs, untouched.
Metrics gain the realized-cohort counters (``PARTICIPATION_METRIC_
KEYS``) and ``comm.metering.realized_wire_metrics`` replaces the
configured byte totals with realized ones (corrupt uploads still
spend uplink bytes; duplicates spend them twice; drops spend none).

STREAMING AGGREGATION (unbounded K, ``FederatedConfig.stream_chunk``):
the vmap driver above still materializes the cohort's uploads as a
(K, lanes) slab before reducing, so device memory — not the wire —
caps K.  With ``stream_chunk=C > 0`` the round becomes a ``lax.scan``
over ceil(K/C) upload chunks whose carry IS the server state: the
unnormalized uint32 weighted vote counts (plus f32 dense sums, the
uint32 weight sum, and the realized-cohort counters).  Each scan step
trains one chunk of C clients, runs the SAME per-upload fault pipeline
(draws key on the global client id, so scenarios replay bit-
identically), and folds the chunk's lanes into the accumulator
(``comm.protocol`` ``fold_stacked_*`` -> ``comm.bitpack.packed_
weighted_fold``).  Integer addition is associative, so after the one
reciprocal normalization at the end the scores are BIT-IDENTICAL to
the slab path at any K and chunk size (tests/test_streaming.py);
peak upload memory is O(C·n) whatever K is, and a straggler past the
cutoff is simply an upload never folded in.  A non-dividing last chunk
is padded with weight-0, live-masked replays of leading clients —
excluded from every count.  Host-side, ``train.fit.streamed_
federated_fit`` double-buffers the NEXT cohort's batches onto the
device (``jax.device_put``) under the current round's dispatched
compute.

Two execution paths with identical math AND identical draws (the
per-client draw words coincide, so the two paths produce bit-identical
scores for the same key/round_index):
  * ``federated_round``        — vmap over a stacked client axis
    (CPU simulation; the paper's 10-client experiments).  The
    fused ``w = Q·Bern(f(s))`` inside each client's forward/backward
    does NOT pay K-times Q regeneration: ``kernels.ops`` installs
    custom_vmap rules on the fused custom_vjp, so this vmap lowers
    onto the natively-batched fused kernels (p-slab in-block, one
    hash-RNG generation per row block).
  * ``sharded_client_update``  — the piece that runs inside
    ``shard_map`` on the production mesh, where the client axis IS the
    ``data`` mesh axis and aggregation is the transport's collective:
    the psum / all-gather of packed mask lanes replaces the f32
    gradient all-reduce of standard data parallelism.

Multi-round driving (one compile per (K, E) shape, rounds + the round
counter carried through ``lax.scan``) lives in ``train.fit``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..comm.downlink import codec_names, get_codec
from ..comm.metering import (
    realized_wire_metrics,
    round_wire_report,
    scheduled_wire_metrics,
)
from ..comm.protocol import resolve_transport, transport_names
from ..optim import Optimizer, sgd
from ..tracing import (FED_AGGREGATE, FED_DOWNLINK, FED_MODEL, FED_UPDATE,
                       FED_UPLOAD)
from .sampling import (as_word, clip_probs, fold_word,
                       quant_threshold_u24_dyn)
from .zampling import (
    MaskProgram,
    ZamplingSpecs,
    infer_downlink,
    validate_carried,
    validate_mask_mode,
)

LossFn = Callable[[Any, Any], jnp.ndarray]  # (params, batch) -> scalar

_MASK_PATHS = ("fused", "composed")

# downlink rate-control schedules (FederatedConfig.downlink_schedule):
#   constant — every round broadcasts at the codec's full width; the
#              plain fixed-codec path runs untouched (b_vec is None)
#   cosine   — anneal the width from schedule_b_min up to codec.bits
#              over schedule_rounds rounds (coarse early rounds, full
#              precision at convergence)
#   frontier — per-tensor widths adapted from MEASURED score dynamics:
#              the fraction of draw words that would flip between b and
#              b+2 bits, computed on the already-encoded carry
DOWNLINK_SCHEDULES = ("constant", "cosine", "frontier")


@dataclass(frozen=True)
class FederatedConfig:
    num_clients: int = 10
    local_steps: int = 1  # "epochs" per round in the paper (up to 100)
    local_lr: float = 0.1
    mode: str = "sample"  # sample | continuous | discretize
    aggregate: str = "mean"  # a registered comm.protocol transport name
    mask_path: str = "fused"  # fused | composed (the bit-exact oracle)
    downlink: str = "f32"  # a registered comm.downlink codec name
    # partial participation: a round whose SURVIVING cohort (arrived
    # AND validated) is smaller than this is skipped — state carried
    # forward unchanged, metrics flag round_skipped
    min_clients: int = 1
    # streaming aggregation: fold uploads into the (n,) vote-count
    # accumulator in chunks of this many clients (lax.scan carry), so
    # the (K, n) upload slab never materializes and peak upload memory
    # is O(stream_chunk * n) whatever K is.  0 (default) = the one-shot
    # slab path; a chunk >= K also falls through to it (one chunk IS
    # the slab).  Scores are bit-identical either way.
    stream_chunk: int = 0
    # adaptive downlink rate control (DOWNLINK_SCHEDULES): the round's
    # broadcast is re-quantized at a per-round (frontier: per-tensor)
    # width b <= codec.bits.  The CARRY stays the codec's fixed-width
    # wire representation (the scheduled word is widened by the exact
    # divisor embedding, comm.downlink.QuantizedDown.encode_at), so the
    # width vector is a TRACED per-round value — R rounds compile once
    # and every carry consumer (fused kernels, serve, checkpoint) stays
    # on the static fast path.  Only b bits/coord are metered as
    # crossing the wire (the widening is a shared deterministic map).
    downlink_schedule: str = "constant"
    schedule_b_min: int = 2  # the schedules' floor width
    schedule_rounds: int = 0  # cosine anneal horizon (rounds)
    # frontier controller: raise b by 2 when the measured draw-word
    # flip fraction between b and b+2 exceeds this; lower b by 2 when
    # it falls under a quarter of it
    frontier_threshold: float = 0.02

    def __post_init__(self):
        if self.min_clients < 1:
            raise ValueError(
                f"min_clients must be >= 1, got {self.min_clients}"
            )
        if self.stream_chunk < 0:
            raise ValueError(
                f"stream_chunk must be >= 0 (0 = slab path), got "
                f"{self.stream_chunk}"
            )
        if self.aggregate not in transport_names():
            raise ValueError(
                f"unknown aggregate strategy {self.aggregate!r}; "
                f"registered transports: {', '.join(transport_names())}"
            )
        if self.downlink not in codec_names():
            raise ValueError(
                f"unknown downlink codec {self.downlink!r}; "
                f"registered codecs: {', '.join(codec_names())}"
            )
        validate_mask_mode(self.mode)
        if self.mask_path not in _MASK_PATHS:
            raise ValueError(
                f"unknown mask_path {self.mask_path!r}; valid paths: "
                f"{', '.join(_MASK_PATHS)}"
            )
        if self.downlink_schedule not in DOWNLINK_SCHEDULES:
            raise ValueError(
                f"unknown downlink_schedule {self.downlink_schedule!r}; "
                f"valid schedules: {', '.join(DOWNLINK_SCHEDULES)}"
            )
        if self.downlink_schedule != "constant":
            codec = get_codec(self.downlink)
            if not codec.quantized:
                raise ValueError(
                    f"downlink_schedule={self.downlink_schedule!r} needs "
                    f"a quantized downlink codec to rate-control; "
                    f"{self.downlink!r} is not quantized"
                )
            if not 1 <= self.schedule_b_min <= codec.bits:
                raise ValueError(
                    f"schedule_b_min must be in [1, {codec.bits}] for "
                    f"downlink codec {self.downlink!r}, got "
                    f"{self.schedule_b_min}"
                )
            if (self.downlink_schedule == "cosine"
                    and self.schedule_rounds < 1):
                raise ValueError(
                    "downlink_schedule='cosine' needs schedule_rounds "
                    f">= 1 (the anneal horizon), got "
                    f"{self.schedule_rounds}"
                )
            if (self.downlink_schedule == "frontier"
                    and self.frontier_threshold <= 0):
                raise ValueError(
                    "frontier_threshold must be > 0, got "
                    f"{self.frontier_threshold}"
                )


def mask_program(zspecs: ZamplingSpecs, cfg: FederatedConfig) -> MaskProgram:
    """The round's configured mask lifecycle: mode x fused x packed.

    THE single definition of the packed-wire predicate: the resolved
    transport's ``packed_wire`` (``resolve_transport`` already
    downgrades continuous — the only non-binary upload — to
    ``mean_f32``).  ``local_update`` emits what this program's
    ``packed`` says, and the aggregators in ``federated_round`` /
    ``sharded_client_update`` branch on the SAME field — never
    recompute the predicate elsewhere.
    """
    transport = resolve_transport(cfg.aggregate, cfg.mode)
    return MaskProgram(
        zspecs,
        mode=cfg.mode,
        fused=cfg.mask_path == "fused",
        packed=transport.packed_wire,
        downlink=cfg.downlink,
    )


def _with_schedule_state(zspecs: ZamplingSpecs, cfg: FederatedConfig,
                         state):
    """Attach the frontier schedule's carried per-tensor width vector
    to an encoded state (identity for the other schedules, and for a
    state that already carries one).  Widths start at the floor
    ``schedule_b_min`` — the controller raises them as the measured
    score dynamics demand."""
    if cfg.downlink_schedule != "frontier" or "downlink_b" in state:
        return state
    b0 = jnp.full((len(zspecs.specs),), cfg.schedule_b_min, jnp.uint32)
    return {**state, "downlink_b": b0}


def encode_state(zspecs: ZamplingSpecs, cfg: FederatedConfig, state,
                 word=0):
    """Encode an f32 score state into ``cfg.downlink``'s wire
    representation — what the round drivers carry.  ``word`` keys the
    dither stream (use the same derivation as the round that WOULD
    have produced this broadcast; 0 for an init state).  Identity for
    ``downlink='f32'``.  Idempotent: a state already carrying
    ``cfg.downlink``'s wire words passes through unchanged (encoding
    wire words as if they were f32 scores would saturate them all to
    the top code); a state encoded with a DIFFERENT codec raises.  The
    match is a full SIGNATURE check (dtype + packed lane count, the
    explicit-tag validation of ``core.zampling.validate_carried``) —
    the packed sub-byte codecs all share the uint32 carrier, so dtype
    sniffing alone cannot tell them apart.  With the frontier schedule
    the returned state additionally carries the per-tensor width
    vector ``state['downlink_b']``."""
    codec = get_codec(cfg.downlink)
    try:
        validate_carried(zspecs, state["scores"], codec.name)
        return _with_schedule_state(zspecs, cfg, state)
    except ValueError:
        pass
    carried = infer_downlink(state["scores"])
    if carried != "f32":
        raise ValueError(
            f"state is already encoded with downlink codec {carried!r}; "
            f"decode_state it first before re-encoding as "
            f"{codec.name!r}"
        )
    if not codec.quantized:
        return _with_schedule_state(zspecs, cfg, state)
    w = as_word(word)
    scores = {
        path: codec.encode(spec, state["scores"][path], w)
        for path, spec in zspecs.specs.items()
    }
    return _with_schedule_state(zspecs, cfg, {**state, "scores": scores})


def decode_state(zspecs: ZamplingSpecs, cfg: FederatedConfig, state):
    """Wire-encoded round carry -> f32 score state (server-side
    analysis helper; the lossy inverse of ``encode_state``)."""
    program = mask_program(zspecs, cfg)
    return {**state, "scores": program.decode_scores(state["scores"])}


def local_update(
    zspecs: ZamplingSpecs,
    state: Dict[str, Any],
    loss_fn: LossFn,
    batches,  # (local_steps, ...) stacked client batches
    key,  # PRNG key or uint32 draw word identifying (round, client)
    cfg: FederatedConfig,
    opt: Optional[Optimizer] = None,
    constraints=None,
    row_sharding=None,
):
    """One client's round: E local score-steps -> the upload draw.

    Returns (z_new, dense_new, mean_loss); ``z_new`` is {path: uint32
    wire lanes} when the configured transport is packed (sample mode),
    else {path: f32 masks/probs}.  Dense (non-reparametrized) leaves
    are trained locally too and aggregated by plain averaging (they are
    tiny: norms/biases).

    Draw keying: local step ``e`` draws at word ``fold_word(kw, e)``
    and the upload at ``fold_word(kw, E)``, where ``kw = as_word(key)``
    — the integer step counter is the scanned xs, so the in-kernel
    draw of the fused path and this oracle generate identical bits.

    ``state['scores']`` arrives in ``cfg.downlink``'s wire
    representation (the encoded broadcast); the client decodes its own
    TRAINABLE copy here — identity for the ``f32`` oracle codec, the
    exact widened-threshold probabilities for the quantized codecs.
    """
    opt = opt or sgd(cfg.local_lr)
    program = mask_program(zspecs, cfg)
    kw = as_word(key)
    with jax.named_scope(FED_DOWNLINK):
        scores0 = program.decode_scores(state["scores"])
    dense0 = dict(state["dense"])

    def loss_of(trainable, batch, step_word):
        # the weights call holds the reconstruct kernel: no scope around it
        params = program.weights(
            trainable["scores"], trainable["dense"], step_word,
            constraints=constraints, row_sharding=row_sharding,
        )
        with jax.named_scope(FED_MODEL):
            return loss_fn(params, batch)

    def step(carry, xs):
        trainable, opt_state = carry
        batch, e = xs
        with jax.named_scope(FED_MODEL):
            word = fold_word(kw, e)
        loss, grads = jax.value_and_grad(loss_of)(trainable, batch, word)
        with jax.named_scope(FED_UPDATE):
            updates, opt_state = opt.update(grads, opt_state, trainable)
            trainable = jax.tree.map(lambda p, u: p + u, trainable, updates)
        return (trainable, opt_state), loss

    trainable0 = {"scores": scores0, "dense": dense0}
    steps = jnp.arange(cfg.local_steps, dtype=jnp.uint32)
    (trainable, _), losses = jax.lax.scan(
        step, (trainable0, opt.init(trainable0)), (batches, steps)
    )

    # p_new = f(s_new); z_new ~ Bern(p_new) — the n bits sent upstream,
    # drawn at the next counter value (E) and emitted as wire lanes on
    # the packed transports (fused: in-kernel, no f32 mask slab).
    with jax.named_scope(FED_UPLOAD):
        z_new = program.upload(trainable["scores"],
                               fold_word(kw, cfg.local_steps))
    return z_new, trainable["dense"], jnp.mean(losses)


# byte-count keys every round's metrics dict carries (comm.metering);
# launch code sizing shard_map out_specs keys off the metrics tree uses
# this instead of hardcoding {"loss"}
WIRE_METRIC_KEYS = (
    "uplink_bytes_per_client",
    "uplink_bytes_round",
    "downlink_bytes_per_client",
    "downlink_bytes_round",
    "naive_uplink_bytes_per_client",
)

# realized-cohort counters (partial participation; repro.fault) — the
# plain full-participation round reports them too (all clients
# participating, nothing skipped), so EVERY round's metrics dict has
# the identical key set and shard_map out_specs never depend on the
# participation arguments
PARTICIPATION_METRIC_KEYS = (
    "cohort_size",
    "num_participating",
    "num_dropped",
    "num_stragglers",
    "num_corrupt",
    "num_duplicates",
    "weight_sum",
    "round_skipped",
)

# THE key set of a round's metrics dict: size shard_map out_specs from
# this (tests/_helpers.round_metric_specs, launch.dryrun), never from
# a hardcoded subset
ROUND_METRIC_KEYS = ("loss",) + WIRE_METRIC_KEYS + PARTICIPATION_METRIC_KEYS


def _wire_metrics(zspecs: ZamplingSpecs, cfg: FederatedConfig,
                  num_clients: int, b_vec=None):
    """Exact byte counts for this round's traffic (static per config).

    ``num_clients`` is the round's REALIZED cohort size — the stacked
    batch's leading axis on the vmap path, the mesh axis size on the
    sharded path — never ``cfg.num_clients``, which only names the
    default population size.

    ``b_vec``: a scheduled round's traced per-tensor width vector —
    the downlink counts are overridden with the REALIZED bits at those
    widths (``comm.metering.scheduled_wire_metrics``: lane packing and
    padding included), so the metrics report what actually crossed the
    wire, not the carry's configured width.  Key set unchanged (values
    become traced f32).
    """
    rep = round_wire_report(
        zspecs, cfg.aggregate, num_clients,
        mode=cfg.mode, downlink=cfg.downlink,
    )
    out = {k: rep[k] for k in WIRE_METRIC_KEYS}
    if b_vec is not None:
        sched = scheduled_wire_metrics(out, zspecs, b_vec, num_clients)
        out = {k: sched[k] for k in WIRE_METRIC_KEYS}
    return out


def _full_participation_metrics(k: int):
    """The participation counters of a plain full-participation round:
    everyone sampled, everyone weight 1, nothing faulted or skipped."""
    return {
        "cohort_size": float(k),
        "num_participating": float(k),
        "num_dropped": 0.0,
        "num_stragglers": 0.0,
        "num_corrupt": 0.0,
        "num_duplicates": 0.0,
        "weight_sum": float(k),
        "round_skipped": 0.0,
    }


@jax.named_scope(FED_DOWNLINK)
def _encode_scores(zspecs: ZamplingSpecs, cfg: FederatedConfig,
                   scores, key, round_index, b_vec=None):
    """Re-encode the aggregated p(t+1) as the next round's broadcast.

    The dither word ``fold_word(key_word(key), round_index)`` is
    derived from REPLICATED values only, so the vmap server and every
    shard_map shard produce bit-identical encodings (the dither stream
    has its own counter space — it can never alias a client draw
    word).  Identity for ``downlink='f32'``.

    ``b_vec``: the scheduled round's traced per-tensor widths — tensor
    i quantizes at ``b_vec[i]`` bits and the scheduled word is widened
    into the codec's fixed carry width by the exact divisor embedding
    (``encode_at``); only b bits/coord cross the wire.  ``None`` (the
    constant schedule) is the plain fixed-width path, bitwise
    untouched.
    """
    codec = get_codec(cfg.downlink)
    if not codec.quantized:
        return scores
    w = fold_word(as_word(key), jnp.asarray(round_index).astype(jnp.uint32))
    if b_vec is None:
        return {
            path: codec.encode(spec, scores[path], w)
            for path, spec in zspecs.specs.items()
        }
    return {
        path: codec.encode_at(spec, scores[path], w, b_vec[i])
        for i, (path, spec) in enumerate(zspecs.specs.items())
    }


@jax.named_scope(FED_DOWNLINK)
def _round_b_vec(zspecs: ZamplingSpecs, cfg: FederatedConfig, state,
                 round_index):
    """This round's per-tensor downlink width vector (traced uint32),
    or ``None`` on the constant schedule (the plain fixed-codec path).

    cosine: one width for every tensor, annealed from
    ``schedule_b_min`` up to the codec's full width over
    ``schedule_rounds`` rounds (half-cosine, clamped at the horizon) —
    coarse broadcasts while the scores are still moving fast, full
    precision at convergence.  frontier: the carried measured widths
    ``state['downlink_b']`` (updated per round by
    ``_frontier_next_b``).  Both are functions of traced per-round
    values only, so an R-round scan compiles ONCE.
    """
    if cfg.downlink_schedule == "constant":
        return None
    if cfg.downlink_schedule == "frontier":
        b = state.get("downlink_b")
        if b is None:  # direct round call without encode_state
            b = jnp.full((len(zspecs.specs),), cfg.schedule_b_min,
                         jnp.uint32)
        return jnp.asarray(b).astype(jnp.uint32)
    codec = get_codec(cfg.downlink)
    horizon = jnp.float32(cfg.schedule_rounds)
    t = jnp.minimum(jnp.asarray(round_index).astype(jnp.float32), horizon)
    span = jnp.float32(codec.bits - cfg.schedule_b_min)
    b = (jnp.float32(cfg.schedule_b_min)
         + span * (1.0 - jnp.cos(jnp.pi * t / horizon)) * 0.5)
    b = jnp.clip(jnp.round(b), cfg.schedule_b_min, codec.bits)
    return jnp.full((len(zspecs.specs),), 1, jnp.uint32) * b.astype(
        jnp.uint32)


def _flip_fraction(p, b, b_hi):
    """Expected fraction of draw words that flip between widths ``b``
    and ``b_hi`` for probabilities ``p``: the draw at width b fires
    iff ``(u >> 8) < T_b``, so for a uniform word the flip probability
    at one coordinate is ``|T_b - T_hi| * 2^-24`` — no dither, no
    draws: a deterministic probe of how much probability mass the
    coarser lattice is displacing."""
    def thr(bits):
        bf = ((jnp.uint32(1) << bits) - jnp.uint32(1)).astype(jnp.float32)
        q = jnp.clip(jnp.floor(p * bf + 0.5), 0.0, bf).astype(jnp.uint32)
        return quant_threshold_u24_dyn(q, bits)

    t_lo, t_hi = thr(b), thr(b_hi)
    diff = jnp.where(t_lo > t_hi, t_lo - t_hi, t_hi - t_lo)
    return jnp.mean(diff.astype(jnp.float32)) * jnp.float32(2.0 ** -24)


def _frontier_next_b(zspecs: ZamplingSpecs, cfg: FederatedConfig,
                     agg, b_vec):
    """The frontier controller: next round's per-tensor widths from
    the round's f32 aggregate — the scores ABOUT to be encoded, probed
    BEFORE the lattice coarsens them (the decoded b-bit carry sits
    exactly on the b-bit lattice, so a post-encode probe would read a
    flip fraction of zero forever).  Tensor i probes the draw-word
    flip fraction between its current width b and b+2
    (``_flip_fraction``; the aggregate is replicated post-collective,
    so every shard computes the identical widths); flips above
    ``frontier_threshold`` mean the coarse lattice is audibly
    displacing mass -> widen by 2, flips under a quarter of it mean
    precision is being wasted -> narrow by 2.  Clamped to
    [schedule_b_min, codec.bits]."""
    codec = get_codec(cfg.downlink)
    b_max = jnp.uint32(codec.bits)
    nxt = []
    for i, (path, spec) in enumerate(zspecs.specs.items()):
        b = b_vec[i]
        p = clip_probs(jnp.asarray(agg[path], jnp.float32))
        flip = _flip_fraction(p, b, jnp.minimum(b + jnp.uint32(2), b_max))
        up = flip > jnp.float32(cfg.frontier_threshold)
        down = flip < jnp.float32(cfg.frontier_threshold / 4.0)
        nb = jnp.where(up, b + jnp.uint32(2),
                       jnp.where(down & (b > jnp.uint32(2)),
                                 b - jnp.uint32(2), b))
        nxt.append(jnp.clip(nb, jnp.uint32(cfg.schedule_b_min), b_max))
    return jnp.stack(nxt)


@jax.named_scope(FED_DOWNLINK)
def _schedule_state_out(zspecs: ZamplingSpecs, cfg: FederatedConfig,
                        agg, state, b_vec, skip=None):
    """The extra carried leaves of a scheduled round's output state
    (frontier's width vector, measured on the round's f32 aggregate;
    empty otherwise).  On a skipped round the widths pass through
    unchanged with the rest of the carry."""
    if cfg.downlink_schedule != "frontier":
        return {}
    nb = _frontier_next_b(zspecs, cfg, agg, b_vec)
    if skip is not None:
        nb = jnp.where(skip, jnp.asarray(state["downlink_b"],
                                         jnp.uint32), nb)
    return {"downlink_b": nb}


def _aggregate_stacked(zspecs, transport, packed, z_all):
    """Server reduction over the stacked client axis, packed or f32."""
    if packed:
        return {
            p: transport.aggregate_stacked_packed(z_all[p],
                                                  zspecs.specs[p].n)
            for p in z_all
        }
    return {p: transport.aggregate_stacked(z) for p, z in z_all.items()}


def _resolve_faults(zspecs, packed, z_all, faults, round_index, ids):
    """Shared per-upload fault pipeline of both drivers.

    ``z_all``/``ids`` carry a (K,) client axis on the vmap path and are
    per-shard (no client axis) under shard_map — the draws key on the
    CLIENT ID either way, so the scenarios coincide bit-for-bit.
    Returns (z_wire, codes, arrived, participating): the uploads as
    the server RECEIVES them (corruption applied), the per-client
    fault codes, the arrival bits (bytes on the wire), and
    ``arrived & validated`` (counted in the aggregate).
    """
    # late import: core.federated is imported by repro.core's __init__,
    # while repro.fault imports core.hashrng — binding at trace time
    # keeps the package import order acyclic in both directions
    from ..fault.plan import CORRUPT, DROP, STRAGGLER, corrupt_uploads, draw_faults
    from ..fault.validate import upload_counts, validate_uploads

    declared = upload_counts(z_all, zspecs, packed)
    if faults is not None:
        codes = draw_faults(faults, round_index, ids)
        z_wire = corrupt_uploads(faults, z_all, declared, codes == CORRUPT,
                                 round_index, ids, zspecs, packed)
    else:
        codes = jnp.zeros(jnp.shape(ids), jnp.uint32)
        z_wire = z_all
    arrived = (codes != DROP) & (codes != STRAGGLER)
    # server-side validation runs on the RECEIVED payload — the genuine
    # check, not a read-back of the injector's corrupt flag
    valid = validate_uploads(z_wire, declared, zspecs, packed)
    return z_wire, codes, arrived, arrived & valid


def _fault_counts(codes, arrived, participating, live=None):
    """Realized-cohort counters from per-client fault state (f32).

    ``live`` masks out the padding lanes of a streaming chunk (the last
    chunk is padded up to ``stream_chunk`` with replayed clients at
    weight 0) — a padded lane must not count anywhere."""
    from ..fault.plan import DROP, DUPLICATE, STRAGGLER

    def cnt(mask):
        if live is not None:
            mask = mask & live
        return jnp.sum(mask.astype(jnp.float32))

    dup = cnt(codes == DUPLICATE)
    return {
        "num_participating": cnt(participating),
        "num_dropped": cnt(codes == DROP),
        "num_stragglers": cnt(codes == STRAGGLER),
        "num_corrupt": cnt(arrived & ~participating),
        "num_duplicates": dup,
        # arrivals spend uplink bytes even when validation rejects
        # them; each duplicate upload arrives twice
        "uplink_units": cnt(arrived) + dup,
    }


# streaming-carry counter keys: the f32 scalars accumulated across
# chunks alongside the vote counts (uplink_units is popped into the
# realized byte metrics, the rest are PARTICIPATION_METRIC_KEYS)
_STREAM_COUNTER_KEYS = ("num_participating", "num_dropped",
                        "num_stragglers", "num_corrupt",
                        "num_duplicates", "uplink_units")


def _streaming_round(zspecs, state, loss_fn, client_batches, key, cfg,
                     opt, transport, packed, *, round_index, ids, w,
                     faults, k):
    """The unbounded-K round: a ``lax.scan`` over upload CHUNKS with
    the unnormalized weighted vote counts as carry.

    The slab round materializes every client's upload as a (K, lanes)
    stack before reducing, so device memory — not the wire — caps K.
    Here the K clients are processed ``stream_chunk`` at a time: each
    scan step runs the chunk's local updates, applies the per-upload
    fault pipeline (``_resolve_faults`` is shape-polymorphic over the
    leading axis, so draws still key on the GLOBAL client id and any
    fault scenario replays bit-identically), and FOLDS the chunk's
    uploads into the carry via the transport's ``fold_stacked_*``
    hooks.  Peak upload memory is O(chunk·n), independent of K.

    Carry = {uint32 (or exact-integer f32) vote counts per tensor, f32
    weighted dense sums, uint32 weight sum, f32 loss sum, f32 fault
    counters}.  Integer sums are associative, so after the final
    reciprocal normalization the scores are BIT-IDENTICAL to the slab
    path at any K and chunk size; dense leaves and loss are f32 sums
    re-associated across chunks (allclose, not bitwise — same contract
    as the cross-driver comparison).

    ``k % stream_chunk != 0`` pads the last chunk by replaying leading
    clients at weight 0 under a ``live=False`` mask: a padded lane
    replays a real client's fault draw and upload but is excluded from
    the vote counts, the weight sum, every counter, and the loss.
    """
    chunk = cfg.stream_chunk
    nchunks = -(-k // chunk)
    pad = nchunks * chunk - k

    def chunked(x):
        if pad:
            x = jnp.concatenate([x, x[:pad]], axis=0)
        return x.reshape((nchunks, chunk) + x.shape[1:])

    live = jnp.arange(nchunks * chunk, dtype=jnp.uint32) < jnp.uint32(k)
    xs = {
        "batches": jax.tree.map(chunked, client_batches),
        "ids": chunked(ids),
        "w": chunked(w),
        "live": live.reshape(nchunks, chunk),
    }
    rword = jnp.asarray(round_index).astype(jnp.uint32)

    def one(batches, word):
        return local_update(zspecs, state, loss_fn, batches, word, cfg,
                            opt)

    carry0 = {
        "votes": {p: transport.stream_init(spec.n)
                  for p, spec in zspecs.specs.items()},
        "dense": jax.tree.map(
            lambda d: jnp.zeros(jnp.shape(d), jnp.float32),
            dict(state["dense"]),
        ),
        "wsum": jnp.uint32(0),
        "loss": jnp.float32(0),
        **{c: jnp.float32(0) for c in _STREAM_COUNTER_KEYS},
    }

    def body(carry, x):
        words = fold_word(as_word(key), rword, x["ids"])
        z_all, dense_all, losses = jax.vmap(one)(x["batches"], words)
        with jax.named_scope(FED_AGGREGATE):
            z_wire, codes, arrived, participating = _resolve_faults(
                zspecs, packed, z_all, faults, round_index, x["ids"])
            chunk_live = x["live"]
            participating = participating & chunk_live
            w_eff = x["w"] * participating.astype(jnp.uint32)
            if packed:
                votes = {
                    p: transport.fold_stacked_packed_weighted(
                        carry["votes"][p], z_wire[p], zspecs.specs[p].n,
                        w_eff)
                    for p in z_wire
                }
            else:
                votes = {
                    p: transport.fold_stacked_weighted(carry["votes"][p], z,
                                                       w_eff)
                    for p, z in z_wire.items()
                }
            w_f = w_eff.astype(jnp.float32)

            def dense_fold(acc, d):
                wcol = w_f.reshape((chunk,) + (1,) * (d.ndim - 1))
                return acc + jnp.sum(d * wcol, axis=0)

            counts = _fault_counts(codes, arrived, participating,
                                   live=chunk_live)
            new = {
                "votes": votes,
                "dense": jax.tree.map(dense_fold, carry["dense"], dense_all),
                "wsum": carry["wsum"] + jnp.sum(w_eff, dtype=jnp.uint32),
                "loss": carry["loss"] + jnp.sum(
                    losses * participating.astype(jnp.float32)),
                **{c: carry[c] + counts[c] for c in _STREAM_COUNTER_KEYS},
            }
        return new, None

    acc, _ = jax.lax.scan(body, carry0, xs)
    with jax.named_scope(FED_AGGREGATE):
        wsum = acc["wsum"].astype(jnp.float32)
        safe_wsum = jnp.where(wsum > 0, wsum, jnp.float32(1))
        # reciprocal form, matching the slab participation branch — see
        # federated_round
        recip = jnp.float32(1.0) / safe_wsum
        agg = {
            p: (v.astype(jnp.float32) if packed else v) * recip
            for p, v in acc["votes"].items()
        }
        b_vec = _round_b_vec(zspecs, cfg, state, round_index)
        new_enc = _encode_scores(zspecs, cfg, agg, key, round_index, b_vec)
        new_dense_agg = jax.tree.map(lambda a: a * recip, acc["dense"])
        skip = acc["num_participating"] < cfg.min_clients
        new_scores = {
            p: jnp.where(skip, state["scores"][p], new_enc[p])
            for p in new_enc
        }
        new_dense = jax.tree.map(
            lambda old, new: jnp.where(skip, old, new),
            dict(state["dense"]), new_dense_agg,
        )
        cnt = acc["num_participating"]
        safe_cnt = jnp.where(cnt > 0, cnt, jnp.float32(1))
        loss = acc["loss"] * (jnp.float32(1.0) / safe_cnt)
        metrics = {
            "loss": loss,
            **realized_wire_metrics(_wire_metrics(zspecs, cfg, k, b_vec),
                                    acc["uplink_units"], k),
            "cohort_size": float(k),
            **{c: acc[c] for c in _STREAM_COUNTER_KEYS
               if c != "uplink_units"},
            "weight_sum": wsum,
            "round_skipped": skip.astype(jnp.float32),
        }
        return {"scores": new_scores, "dense": new_dense,
                **_schedule_state_out(zspecs, cfg, agg, state, b_vec,
                                      skip)}, metrics


def federated_round(
    zspecs: ZamplingSpecs,
    state: Dict[str, Any],
    loss_fn: LossFn,
    client_batches,  # pytree with leading axes (K, local_steps, ...)
    key,
    cfg: FederatedConfig,
    opt: Optional[Optimizer] = None,
    *,
    round_index=0,
    client_ids=None,  # (K,) uint32 cohort ids; None = arange(K)
    weights=None,  # (K,) uint32 sample-count weights; None = all ones
    faults: Optional["FaultPlan"] = None,  # noqa: F821 — repro.fault
):
    """Full round over K stacked clients (vmap). Returns (state', metrics).

    ``round_index``: the round counter folded into every draw word
    (threaded by ``train.fit.federated_fit``'s scan); client k draws
    from word ``hash(key_word(key), round_index, client_id_k)``.

    ``client_ids`` / ``weights`` / ``faults`` switch on the
    partial-participation path (weighted aggregation over the realized
    cohort, skip below ``cfg.min_clients``; see the module docstring).
    With all three None the plain full-participation protocol runs —
    the exact PR-5 code path, bit for bit.  K is the stacked batch's
    leading axis; ``cfg.num_clients`` only names the default
    population.

    ``cfg.stream_chunk > 0`` (and < K) reroutes to the streaming
    accumulator (``_streaming_round``): same signature, same metrics
    key set, bit-identical scores, O(stream_chunk·n) peak upload
    memory instead of O(K·n).
    """
    transport = resolve_transport(cfg.aggregate, cfg.mode)
    packed = mask_program(zspecs, cfg).packed
    k = jax.tree.leaves(client_batches)[0].shape[0]
    participation = (client_ids is not None or weights is not None
                     or faults is not None)
    ids = (jnp.arange(k, dtype=jnp.uint32) if client_ids is None
           else jnp.asarray(client_ids).astype(jnp.uint32))
    if cfg.stream_chunk and cfg.stream_chunk < k:
        # streaming aggregation: fold uploads chunk-by-chunk into the
        # vote-count carry; the (K, lanes) slab never materializes and
        # the scores are bit-identical to the slab path below
        w = (jnp.ones((k,), jnp.uint32) if weights is None
             else jnp.asarray(weights).astype(jnp.uint32))
        return _streaming_round(
            zspecs, state, loss_fn, client_batches, key, cfg, opt,
            transport, packed, round_index=round_index, ids=ids, w=w,
            faults=faults, k=k,
        )
    words = fold_word(
        as_word(key), jnp.asarray(round_index).astype(jnp.uint32), ids,
    )

    def one(batches, w):
        return local_update(zspecs, state, loss_fn, batches, w, cfg, opt)

    z_all, dense_all, losses = jax.vmap(one)(client_batches, words)
    with jax.named_scope(FED_AGGREGATE):
        if not participation:
            # server aggregation: p(t+1) = mean_k z^(k), via the wire
            # transport, re-encoded as the next broadcast (cfg.downlink's
            # wire words)
            b_vec = _round_b_vec(zspecs, cfg, state, round_index)
            agg = _aggregate_stacked(zspecs, transport, packed, z_all)
            new_scores = _encode_scores(zspecs, cfg, agg, key, round_index,
                                        b_vec)
            new_dense = jax.tree.map(lambda d: jnp.mean(d, axis=0), dense_all)
            metrics = {"loss": jnp.mean(losses),
                       **_wire_metrics(zspecs, cfg, k, b_vec),
                       **_full_participation_metrics(k)}
            return {"scores": new_scores, "dense": new_dense,
                    **_schedule_state_out(zspecs, cfg, agg, state,
                                          b_vec)}, metrics

        # ---- partial participation: faults -> validation -> weighted mean
        z_wire, codes, arrived, participating = _resolve_faults(
            zspecs, packed, z_all, faults, round_index, ids)
        w = (jnp.ones((k,), jnp.uint32) if weights is None
             else jnp.asarray(weights).astype(jnp.uint32))
        w_eff = w * participating.astype(jnp.uint32)
        wsum = jnp.sum(w_eff, dtype=jnp.uint32).astype(jnp.float32)
        safe_wsum = jnp.where(wsum > 0, wsum, jnp.float32(1))
        # RECIPROCAL form everywhere below, never `x / safe_wsum`: XLA
        # strength-reduces the legacy path's divisions by a CONSTANT count
        # (aggregate_stacked's `/ K`, jnp.mean, psum / axis_size) into a
        # reciprocal multiply, and a runtime `x * (1/w)` reproduces that
        # bit for bit at any K while a true division drifts by an ulp
        # whenever the weight sum is not a power of two
        recip = jnp.float32(1.0) / safe_wsum
        if packed:
            agg = {
                p: transport.aggregate_stacked_packed_weighted(
                    z_wire[p], zspecs.specs[p].n, w_eff
                ).astype(jnp.float32) * recip
                for p in z_wire
            }
        else:
            agg = {
                p: transport.aggregate_stacked_weighted(z, w_eff) * recip
                for p, z in z_wire.items()
            }
        counters = _fault_counts(codes, arrived, participating)
        b_vec = _round_b_vec(zspecs, cfg, state, round_index)
        new_enc = _encode_scores(zspecs, cfg, agg, key, round_index, b_vec)
        w_f = w_eff.astype(jnp.float32)

        def dense_mean(d):
            wcol = w_f.reshape((k,) + (1,) * (d.ndim - 1))
            return jnp.sum(d * wcol, axis=0) * recip

        new_dense_agg = jax.tree.map(dense_mean, dense_all)
        # skip-round: below min_clients the carried state passes through
        # unchanged (averaging a near-empty cohort is sampling noise)
        skip = counters["num_participating"] < cfg.min_clients
        new_scores = {
            p: jnp.where(skip, state["scores"][p], new_enc[p])
            for p in new_enc
        }
        new_dense = jax.tree.map(
            lambda old, new: jnp.where(skip, old, new),
            dict(state["dense"]), new_dense_agg,
        )
        part_f = participating.astype(jnp.float32)
        cnt = counters["num_participating"]
        safe_cnt = jnp.where(cnt > 0, cnt, jnp.float32(1))
        loss = jnp.sum(losses * part_f) * (jnp.float32(1.0) / safe_cnt)
        uplink_units = counters.pop("uplink_units")
        metrics = {
            "loss": loss,
            **realized_wire_metrics(_wire_metrics(zspecs, cfg, k, b_vec),
                                    uplink_units, k),
            "cohort_size": float(k),
            **counters,
            "weight_sum": wsum,
            "round_skipped": skip.astype(jnp.float32),
        }
        return {"scores": new_scores, "dense": new_dense,
                **_schedule_state_out(zspecs, cfg, agg, state, b_vec,
                                      skip)}, metrics


def sharded_client_update(
    zspecs: ZamplingSpecs,
    state: Dict[str, Any],
    loss_fn: LossFn,
    batches,
    key,
    cfg: FederatedConfig,
    *,
    axis_names=("data",),
    opt: Optional[Optimizer] = None,
    constraints=None,
    row_sharding=None,
    round_index=0,
    client_id=None,  # this shard's global client id; None = axis index
    weight=None,  # this shard's uint32 sample-count weight; None = 1
    faults: Optional["FaultPlan"] = None,  # noqa: F821 — repro.fault
):
    """Body to run under ``shard_map``: client id = mesh position.

    The mask aggregation is the ONLY cross-client communication; the
    configured transport decides its wire format — an f32 psum
    (``mean_f32``), a uint32 popcount psum of the packed lanes
    (``psum_u32``), or an all-gather of the raw packed lanes
    (``allgather_packed``) over the client axes.  On the packed
    transports the collective operand IS the lanes the fused kernel
    emitted — no f32 mask slab exists on this path at all.  The draw
    words match ``federated_round``'s (client id = axis index), so the
    two paths are bit-identical for the same key/round_index.

    ``client_id`` / ``weight`` / ``faults`` switch on the
    partial-participation path — fault draws, upload validation, and
    the weighted psum key on the GLOBAL client id (per-shard scalars
    here), so a scenario replays bit-identically against the vmap
    driver run over the same cohort.
    """
    from ..comm.shardmap import axis_size

    transport = resolve_transport(cfg.aggregate, cfg.mode)
    packed = mask_program(zspecs, cfg).packed
    participation = (client_id is not None or weight is not None
                     or faults is not None)
    idx = sum(
        jax.lax.axis_index(a) * 1_000_003 ** i for i, a in enumerate(axis_names)
    )
    my_id = (jnp.asarray(idx) if client_id is None
             else jnp.asarray(client_id)).astype(jnp.uint32)
    word = fold_word(
        as_word(key), jnp.asarray(round_index).astype(jnp.uint32), my_id,
    )
    z_new, dense_new, loss = local_update(
        zspecs, state, loss_fn, batches, word, cfg, opt,
        constraints=constraints, row_sharding=row_sharding,
    )
    nclients = axis_size(axis_names)
    with jax.named_scope(FED_AGGREGATE):
        if not participation:
            if packed:
                new_scores = {
                    p: transport.aggregate_collective_packed(
                        z, zspecs.specs[p].n, axis_names
                    )
                    for p, z in z_new.items()
                }
            else:
                new_scores = {
                    p: transport.aggregate_collective(z, axis_names)
                    for p, z in z_new.items()
                }
            # re-encode the replicated aggregate as the next broadcast: the
            # dither word comes from the replicated (key, round_index), so
            # all shards produce the identical encoding — bit-equal to the
            # vmap path (the schedule's b_vec is likewise a function of
            # replicated values only)
            b_vec = _round_b_vec(zspecs, cfg, state, round_index)
            agg = new_scores
            new_scores = _encode_scores(zspecs, cfg, agg, key,
                                        round_index, b_vec)
            # dense leaves stay on the f32 psum path: XLA:CPU's
            # AllReducePromotion pass aborts on bf16 all-reduces (and f32
            # is the numerically right accumulator anyway)
            new_dense = jax.tree.map(
                lambda d: (jax.lax.psum(d.astype(jnp.float32), axis_names)
                           / nclients).astype(d.dtype),
                dense_new,
            )
            loss = jax.lax.pmean(loss, axis_names)
            # the mesh axis size, not cfg.num_clients, is the real K here
            metrics = {"loss": loss,
                       **_wire_metrics(zspecs, cfg, nclients, b_vec),
                       **_full_participation_metrics(nclients)}
            return {"scores": new_scores, "dense": new_dense,
                    **_schedule_state_out(zspecs, cfg, agg, state,
                                          b_vec)}, metrics

        # ---- partial participation: every per-client quantity is a
        # per-shard scalar; the psums realize the weighted server sum
        z_wire, code, arrived, participating = _resolve_faults(
            zspecs, packed, z_new, faults, round_index, my_id)
        w = (jnp.uint32(1) if weight is None
             else jnp.asarray(weight).astype(jnp.uint32))
        w_eff = w * participating.astype(jnp.uint32)
        wsum = jax.lax.psum(w_eff, tuple(axis_names)).astype(jnp.float32)
        safe_wsum = jnp.where(wsum > 0, wsum, jnp.float32(1))
        # reciprocal form, matching the vmap path and the legacy path's
        # constant divisions after XLA's strength reduction — see
        # federated_round's participation branch
        recip = jnp.float32(1.0) / safe_wsum
        if packed:
            agg = {
                p: transport.aggregate_collective_packed_weighted(
                    z, zspecs.specs[p].n, w_eff, axis_names
                ).astype(jnp.float32) * recip
                for p, z in z_wire.items()
            }
        else:
            agg = {
                p: transport.aggregate_collective_weighted(
                    z, w_eff, axis_names
                ) * recip
                for p, z in z_wire.items()
            }
        b_vec = _round_b_vec(zspecs, cfg, state, round_index)
        new_enc = _encode_scores(zspecs, cfg, agg, key, round_index, b_vec)
        counters = {
            k: jax.lax.psum(v, tuple(axis_names))
            for k, v in _fault_counts(code, arrived, participating).items()
        }
        w_f = w_eff.astype(jnp.float32)
        new_dense_agg = jax.tree.map(
            lambda d: (jax.lax.psum(d.astype(jnp.float32) * w_f, axis_names)
                       * recip).astype(d.dtype),
            dense_new,
        )
        skip = counters["num_participating"] < cfg.min_clients
        new_scores = {
            p: jnp.where(skip, state["scores"][p], new_enc[p])
            for p in new_enc
        }
        new_dense = jax.tree.map(
            lambda old, new: jnp.where(skip, old, new),
            dict(state["dense"]), new_dense_agg,
        )
        cnt = counters["num_participating"]
        safe_cnt = jnp.where(cnt > 0, cnt, jnp.float32(1))
        loss = jax.lax.psum(
            loss * participating.astype(jnp.float32), tuple(axis_names)
        ) * (jnp.float32(1.0) / safe_cnt)
        uplink_units = counters.pop("uplink_units")
        metrics = {
            "loss": loss,
            **realized_wire_metrics(
                _wire_metrics(zspecs, cfg, nclients, b_vec),
                uplink_units, nclients),
            "cohort_size": float(nclients),
            **counters,
            "weight_sum": wsum,
            "round_skipped": skip.astype(jnp.float32),
        }
        return {"scores": new_scores, "dense": new_dense,
                **_schedule_state_out(zspecs, cfg, agg, state, b_vec,
                                      skip)}, metrics
