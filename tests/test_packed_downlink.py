"""Packed sub-byte downlink codecs + adaptive rate schedules.

The contracts pinned here:

 - ``pack_words``/``unpack_words`` are exact inverses for any b in
   [1, 16] at any length (lane padding reads back as zeros);
 - ``packed{b}`` keeps the PR-5 draw contract EXACTLY: the b-bit
   probability words quantize, threshold, and draw bit-identically to
   a word-per-coordinate codec of the same width — the bigint
   ``floor(q * 2^24 / (2^b - 1))`` is the oracle across the full
   alphabet (boundary words and endpoints included);
 - ``quant_threshold_u24_dyn`` (traced width) == the static
   ``quant_threshold_u24`` for every width;
 - ``encode_at`` at the codec's own width is BITWISE ``encode``, and
   the divisor embedding of b into B is the exact threshold embedding
   whenever b | B;
 - the fused kernels (ref, pallas, batched, the serve contractions)
   consume the uint32 lanes directly and match the composed
   unpack -> qhash -> reconstruct oracle bit for bit, without
   materializing an unpacked per-coordinate word slab in the pallas
   jaxpr;
 - scheduled rounds: ``downlink_schedule='constant'`` is bit-identical
   to the equivalent fixed codec on the vmap AND 4-device shard_map
   drivers; ``frontier`` reaches the u8 loss neighborhood at strictly
   fewer cumulative downlink bytes; the frontier width vector and the
   packed uint32 carry round-trip a checkpoint bitwise;
 - routing: the packed codecs share the uint32 carrier, so dtype
   sniffing raises on ambiguity and the explicit ``carried=`` tag is
   the only way in.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm.bitpack import (
    pack_words,
    packed_word_len,
    unpack_words,
    words_per_lane,
)
from repro.comm.downlink import codec_for_dtype, get_codec
from repro.comm.metering import scheduled_downlink_bits
from repro.comm.shardmap import shard_map
from repro.core import (
    FederatedConfig,
    ZamplingConfig,
    build_specs,
    decode_state,
    encode_state,
    init_state,
)
from repro.core.federated import federated_round, sharded_client_update
from repro.core.qspec import make_qspec
from repro.core.sampling import (
    quant_threshold_u24,
    quant_threshold_u24_dyn,
    sample_mask_qhash,
)
from repro.core.zampling import infer_downlink, sample_weights
from repro.kernels import ops

PACKED = ("packed4", "packed2")
SWEEP_BITS = (1, 2, 4, 6, 8, 12, 16)


def _mk(shape=(300, 20), c=8.0, d=5, window=64, seed=7, **kw):
    fan = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
    return make_qspec(1, shape, fan, compression=c, d=d, window=window,
                      seed=seed, **kw)


def _lanes(bits, n, seed=0):
    """Random packed lanes whose every word is a valid b-bit value."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randint(0, 1 << bits, n), jnp.uint32)
    return pack_words(q, bits), q


# ---------------------------------------------------------------------------
# lane layout: pack/unpack round-trip (satellite 3, property tests)
# ---------------------------------------------------------------------------

class TestBitpack:
    @pytest.mark.parametrize("bits", SWEEP_BITS)
    @pytest.mark.parametrize("n", [1, 7, 31, 32, 33, 257])
    def test_round_trip(self, bits, n):
        rng = np.random.RandomState(bits * 1000 + n)
        q = jnp.asarray(rng.randint(0, 1 << bits, n), jnp.uint32)
        lanes = pack_words(q, bits)
        assert lanes.dtype == jnp.uint32
        assert lanes.shape == (packed_word_len(n, bits),)
        np.testing.assert_array_equal(np.asarray(unpack_words(lanes, n, bits)),
                                      np.asarray(q))

    @pytest.mark.parametrize("bits", SWEEP_BITS)
    def test_layout_word_j_at_offset_bj(self, bits):
        """Word j of lane i is coordinate i*wpl + j at bit offset b*j —
        the layout the in-kernel unpack and the serve gather assume."""
        wpl = words_per_lane(bits)
        n = 3 * wpl + max(wpl - 1, 1)
        rng = np.random.RandomState(1)
        q = rng.randint(0, 1 << bits, n)
        lanes = np.asarray(pack_words(jnp.asarray(q, jnp.uint32), bits))
        mask = (1 << bits) - 1
        for i in range(n):
            got = (int(lanes[i // wpl]) >> (bits * (i % wpl))) & mask
            assert got == q[i], (bits, i)
        # lane padding holds zero words
        tail = n % wpl
        if tail:
            for j in range(tail, wpl):
                assert (int(lanes[-1]) >> (bits * j)) & mask == 0

    def test_batched_leading_axes(self):
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randint(0, 16, (3, 45)), jnp.uint32)
        lanes = pack_words(q, 4)
        assert lanes.shape == (3, packed_word_len(45, 4))
        np.testing.assert_array_equal(np.asarray(unpack_words(lanes, 45, 4)),
                                      np.asarray(q))

    def test_invalid_bits_raise(self):
        for bad in (0, 17, 32):
            with pytest.raises(ValueError, match="bits"):
                words_per_lane(bad)


# ---------------------------------------------------------------------------
# the widened threshold vs exact bigint, across the b sweep (satellite 3)
# ---------------------------------------------------------------------------

class TestThresholdSweep:
    @pytest.mark.parametrize("bits", SWEEP_BITS)
    def test_static_matches_bigint_oracle(self, bits):
        """T(q) == floor(q * 2^24 / (2^b - 1)) — exact python bigint
        oracle over the full alphabet (b <= 8) or a boundary-heavy
        sample, endpoints pinned: T(0) == 0, T(S) == 2^24."""
        S = (1 << bits) - 1
        if S <= 4096:
            qs = np.arange(S + 1)
        else:
            rng = np.random.RandomState(bits)
            qs = np.unique(np.concatenate([
                np.arange(0, 300),
                np.array([S // 2 - 1, S // 2, S // 2 + 1,
                          S - 2, S - 1, S]),
                rng.randint(0, S + 1, 4000),
            ]))
        T = np.asarray(quant_threshold_u24(jnp.asarray(qs, jnp.uint32),
                                           bits))
        want = np.array([(int(q) << 24) // S for q in qs], np.uint32)
        np.testing.assert_array_equal(T, want)
        assert int(quant_threshold_u24(jnp.uint32(0), bits)) == 0
        assert int(quant_threshold_u24(jnp.uint32(S), bits)) == 1 << 24

    @pytest.mark.parametrize("bits", SWEEP_BITS)
    def test_dyn_matches_static(self, bits):
        """The traced-width threshold (what the scheduled encode runs
        under scan) == the static one, for every word of the alphabet
        (b <= 12) / a dense sample."""
        S = (1 << bits) - 1
        qs = (np.arange(S + 1) if S <= 4096
              else np.random.RandomState(9).randint(0, S + 1, 8192))
        q = jnp.asarray(qs, jnp.uint32)
        stat = quant_threshold_u24(q, bits)
        dyn = jax.jit(quant_threshold_u24_dyn)(q, jnp.uint32(bits))
        np.testing.assert_array_equal(np.asarray(stat), np.asarray(dyn))

    @pytest.mark.parametrize("bits", [1, 2, 4])
    def test_divisor_embedding_preserves_threshold(self, bits):
        """Widening q_b into the B=8 alphabet by the exact divisor
        embedding q = (q_b*S_B + S_b//2) // S_b preserves the draw
        threshold exactly when b | B — the carry can hold the
        scheduled word at full codec width with zero draw drift."""
        B = 8
        S_b, S_B = (1 << bits) - 1, (1 << B) - 1
        for qb in range(S_b + 1):
            q = (qb * S_B + S_b // 2) // S_b
            t_b = (qb << 24) // S_b
            t_B = (q << 24) // S_B
            assert t_b == t_B, (bits, qb)


# ---------------------------------------------------------------------------
# the packed codecs: encode/decode/draw == word-level contract
# ---------------------------------------------------------------------------

class TestPackedCodec:
    @pytest.mark.parametrize("name", PACKED)
    def test_registry_and_shapes(self, name):
        codec = get_codec(name)
        assert codec.packed and codec.quantized
        assert codec.wire_dtype == jnp.uint32
        spec = _mk()
        assert codec.wire_len(spec.n) == packed_word_len(spec.n, codec.bits)
        assert codec.downlink_bits_per_client(spec.n) == \
            32 * packed_word_len(spec.n, codec.bits)

    def test_aliases(self):
        assert get_codec("u4").name == "packed4"
        assert get_codec("u2").name == "packed2"

    @pytest.mark.parametrize("name", PACKED)
    def test_encode_produces_lanes_decode_unpacks(self, name):
        codec = get_codec(name)
        spec = _mk()
        rng = np.random.RandomState(3)
        scores = jnp.asarray(rng.uniform(-0.2, 1.2, spec.n), jnp.float32)
        wire = codec.encode(spec, scores, jnp.uint32(5))
        assert wire.dtype == jnp.uint32
        assert wire.shape == (packed_word_len(spec.n, codec.bits),)
        words = codec.wire_words(spec, wire)
        assert words.shape == (spec.n,)
        assert int(jnp.max(words)) <= (1 << codec.bits) - 1
        # decode == T(word) * 2^-24, the same expression as u8/u16
        T = np.asarray(quant_threshold_u24(words, codec.bits))
        np.testing.assert_array_equal(
            np.asarray(codec.decode(spec, wire)),
            T.astype(np.float64) * 2.0 ** -24)

    @pytest.mark.parametrize("name", PACKED)
    def test_draw_bit_identical_to_word_level(self, name):
        """The client draw from packed lanes == sample_mask_qhash on
        the unpacked words — Bern(p-hat) at the draw-word level."""
        codec = get_codec(name)
        spec = _mk()
        lanes, q = _lanes(codec.bits, spec.n, seed=4)
        for step in (0, 1, 77):
            z_oracle = sample_mask_qhash(q, codec.bits, spec.seed,
                                         spec.tensor_id, jnp.uint32(step))
            z_packed = sample_mask_qhash(
                codec.wire_words(spec, lanes), codec.bits, spec.seed,
                spec.tensor_id, jnp.uint32(step))
            np.testing.assert_array_equal(np.asarray(z_oracle),
                                          np.asarray(z_packed))

    @pytest.mark.parametrize("name", PACKED)
    def test_encode_at_full_width_is_encode(self, name):
        codec = get_codec(name)
        spec = _mk()
        rng = np.random.RandomState(5)
        scores = jnp.asarray(rng.uniform(-0.1, 1.1, spec.n), jnp.float32)
        w = jnp.uint32(9)
        a = codec.encode(spec, scores, w)
        b = jax.jit(lambda s: codec.encode_at(spec, s, w,
                                              jnp.uint32(codec.bits)))(scores)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_encode_at_scheduled_width_embeds(self):
        """u8's encode_at(b=2) lands every word on the widened 2-bit
        sublattice of the u8 alphabet (divisor embedding), with the
        2-bit threshold."""
        codec = get_codec("u8")
        spec = _mk()
        rng = np.random.RandomState(6)
        scores = jnp.asarray(rng.uniform(0, 1, spec.n), jnp.float32)
        q8 = np.asarray(codec.encode_at(spec, scores, jnp.uint32(3),
                                        jnp.uint32(2)))
        lattice = {(qb * 255 + 1) // 3 for qb in range(4)}
        assert set(np.unique(q8)).issubset(lattice)

    def test_dtype_sniffing_raises_on_uint32_carrier(self):
        with pytest.raises(ValueError, match="packed|ambig|uint32"):
            codec_for_dtype(jnp.uint32)


# ---------------------------------------------------------------------------
# fused kernels on packed lanes == composed oracle, no word slab
# ---------------------------------------------------------------------------

class TestPackedKernels:
    @pytest.mark.parametrize("name", PACKED)
    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_sample_reconstruct_matches_oracle(self, name, impl):
        codec = get_codec(name)
        spec = _mk()
        lanes, q = _lanes(codec.bits, spec.n, seed=10)
        step = jnp.uint32(3)
        got = ops.sample_reconstruct(spec, lanes, step, qbits=codec.bits,
                                     qpacked=True, impl=impl)
        z = sample_mask_qhash(q, codec.bits, spec.seed, spec.tensor_id,
                              step)
        want = ops.reconstruct(spec, z, impl="ref")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("name", PACKED)
    def test_batched_matches_oracle(self, name):
        codec = get_codec(name)
        spec = _mk()
        K = 5
        L = packed_word_len(spec.n, codec.bits)
        rng = np.random.RandomState(11)
        qs = jnp.asarray(rng.randint(0, 1 << codec.bits, (K, spec.n)),
                         jnp.uint32)
        lanes = pack_words(qs, codec.bits)
        assert lanes.shape == (K, L)
        steps = jnp.arange(K, dtype=jnp.uint32)
        got = ops.sample_reconstruct_batched(spec, lanes, steps,
                                             qbits=codec.bits,
                                             qpacked=True, impl="pallas")
        for k in range(K):
            z = sample_mask_qhash(qs[k], codec.bits, spec.seed,
                                  spec.tensor_id, steps[k])
            np.testing.assert_array_equal(
                np.asarray(got[k]),
                np.asarray(ops.reconstruct(spec, z, impl="ref")))

    def test_no_word_slab_in_packed_pallas_jaxpr(self):
        """The packed fused pallas path must unpack lanes IN-BLOCK:
        no (n,) per-coordinate uint32 word slab in its jaxpr.  The ref
        fallback DOES materialize it (detector sanity check)."""
        codec = get_codec("packed4")
        spec = _mk()
        lanes, _ = _lanes(codec.bits, spec.n)
        step = jnp.uint32(0)
        slab = ((spec.n,), "uint32")

        def shapes(jx, acc):
            for eqn in jx.eqns:
                for v in eqn.outvars:
                    aval = getattr(v, "aval", None)
                    if aval is not None and getattr(aval, "dtype", None) \
                            is not None:
                        acc.append((tuple(aval.shape), str(aval.dtype)))
                for param in eqn.params.values():
                    inner = getattr(param, "jaxpr", None)
                    if inner is not None:
                        shapes(inner, acc)
                    elif hasattr(param, "eqns"):
                        shapes(param, acc)
            return acc

        fused = jax.make_jaxpr(
            lambda w: ops.sample_reconstruct(spec, w, step,
                                             qbits=codec.bits,
                                             qpacked=True, impl="pallas")
        )(lanes)
        assert slab not in shapes(fused.jaxpr, []), (
            "packed pallas path materializes the (n,) word slab")

        ref = jax.make_jaxpr(
            lambda w: ops.sample_reconstruct(spec, w, step,
                                             qbits=codec.bits,
                                             qpacked=True, impl="ref")
        )(lanes)
        assert slab in shapes(ref.jaxpr, []), (
            "detector failed: ref oracle should materialize the words")

    @pytest.mark.parametrize("impl", ["ref", "chunked", "pallas"])
    def test_serve_matvec_matches_oracle(self, impl):
        codec = get_codec("packed4")
        spec = _mk()
        lanes, q = _lanes(codec.bits, spec.n, seed=12)
        step = jnp.uint32(7)
        rng = np.random.RandomState(13)
        x = jnp.asarray(rng.randn(spec.shape[0]), jnp.float32)
        got = ops.serve_matvec(spec, lanes, step, x, qbits=codec.bits,
                               qpacked=True, impl=impl)
        z = sample_mask_qhash(q, codec.bits, spec.seed, spec.tensor_id,
                              step)
        W = ops.reconstruct(spec, z, impl="ref").reshape(spec.shape)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(x @ W), rtol=1e-5,
                                   atol=1e-5)

    def test_non_fusable_window_falls_back_to_ref(self):
        """window not divisible by words-per-lane: the fused q-kernels
        must still be exact via the ref fallback (which pays the word
        slab — the documented trade)."""
        codec = get_codec("packed4")
        spec = _mk(window=4)  # 4 % 8 != 0: a lane straddles windows
        lanes, q = _lanes(codec.bits, spec.n, seed=14)
        step = jnp.uint32(2)
        got = ops.sample_reconstruct(spec, lanes, step, qbits=codec.bits,
                                     qpacked=True, impl="pallas")
        z = sample_mask_qhash(q, codec.bits, spec.seed, spec.tensor_id,
                              step)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(ops.reconstruct(spec, z, impl="ref")))


# ---------------------------------------------------------------------------
# federated rounds on the packed carry + schedules
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_setup():
    from repro.data import (client_batch_stream, iid_client_split,
                            make_teacher_dataset)
    from repro.models.mlp import SMALL_DIMS, init_mlp_params

    ds = make_teacher_dataset(n_train=600, n_test=100, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=2.0, d=5, window=128, min_size=256))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    K, E, R = 4, 2, 6
    clients = iid_client_split(ds, K)
    stream = client_batch_stream(clients, 32, E, seed=0)
    rounds = [next(stream) for _ in range(R)]
    batches = {"x": jnp.asarray(np.stack([x for x, _ in rounds])),
               "y": jnp.asarray(np.stack([y for _, y in rounds])),}
    return zspecs, state, batches, K, E, R


def _fit(zspecs, state, batches, cfg, key=0):
    from repro.models.mlp import mlp_loss
    from repro.train import federated_fit

    return jax.jit(
        lambda s, b, k: federated_fit(zspecs, s, mlp_loss, b, k, cfg)
    )(state, batches, jax.random.PRNGKey(key))


class TestPackedRounds:
    @pytest.mark.parametrize("name", PACKED)
    def test_round_carries_lanes(self, fed_setup, name):
        """The packed wire lanes ARE the round carry: uint32, lane
        count per tensor, metered at 32 bits/lane."""
        zspecs, state, batches, K, E, R = fed_setup
        codec = get_codec(name)
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", downlink=name)
        st = encode_state(zspecs, cfg, state)
        st1, mets = _fit(zspecs, st, batches, cfg)
        bits = 0
        for p, spec in zspecs.specs.items():
            L = packed_word_len(spec.n, codec.bits)
            assert st1["scores"][p].dtype == jnp.uint32
            assert st1["scores"][p].shape == (L,)
            bits += 32 * L
        dense = sum(4 * int(np.prod(np.shape(v)))
                    for v in st1["dense"].values())
        want = -(-bits // 8) + dense
        np.testing.assert_allclose(
            np.asarray(mets["downlink_bytes_per_client"]),
            float(want), rtol=1e-6)

    def test_packed4_downlink_an_eighth_of_f32(self, fed_setup):
        """The acceptance gate: packed4 score downlink bytes <= 1/8 of
        the f32 score broadcast + lane slack."""
        zspecs, *_ = fed_setup
        codec = get_codec("packed4")
        score_bytes = sum(
            4 * packed_word_len(s.n, codec.bits)
            for s in zspecs.specs.values())
        f32_bytes = sum(4 * s.n for s in zspecs.specs.values())
        slack = 4 * len(zspecs.specs)  # <= one lane per tensor
        assert score_bytes <= f32_bytes / 8 + slack

    def test_constant_schedule_bitwise_equals_fixed_vmap(self, fed_setup):
        zspecs, state, batches, K, E, R = fed_setup
        base = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                               aggregate="psum_u32", downlink="u8")
        sched = FederatedConfig(num_clients=K, local_steps=E,
                                local_lr=0.1, aggregate="psum_u32",
                                downlink="u8",
                                downlink_schedule="constant")
        st = encode_state(zspecs, base, state)
        a, ma = _fit(zspecs, st, batches, base)
        b, mb = _fit(zspecs, st, batches, sched)
        for p in a["scores"]:
            np.testing.assert_array_equal(np.asarray(a["scores"][p]),
                                          np.asarray(b["scores"][p]))
        assert set(ma) == set(mb)
        np.testing.assert_array_equal(
            np.asarray(ma["downlink_bytes_per_client"]),
            np.asarray(mb["downlink_bytes_per_client"]))

    def test_constant_schedule_bitwise_equals_fixed_shardmap(self,
                                                            fed_setup):
        """Same claim on the 4-device shard_map driver (+ the sharded
        scheduled state matches the vmap one bitwise)."""
        from repro.models.mlp import mlp_loss

        zspecs, state, batches, K, E, R = fed_setup
        mesh = data_mesh_or_skip(4)
        batch0 = jax.tree.map(lambda x: x[0], batches)
        cfgs = {
            "fixed": FederatedConfig(num_clients=K, local_steps=E,
                                     local_lr=0.1, aggregate="psum_u32",
                                     downlink="u8"),
            "sched": FederatedConfig(num_clients=K, local_steps=E,
                                     local_lr=0.1, aggregate="psum_u32",
                                     downlink="u8",
                                     downlink_schedule="constant"),
        }
        outs = {}
        for tag, cfg in cfgs.items():
            st = encode_state(zspecs, cfg, state)
            state_specs = jax.tree.map(lambda _: P(), st)

            def body(s, b, k, cfg=cfg):
                b = jax.tree.map(lambda x: x[0], b)
                return sharded_client_update(zspecs, s, mlp_loss, b, k,
                                             cfg)

            with jax.set_mesh(mesh):
                f = shard_map(body, ("data",),
                              (state_specs, P("data"), P()),
                              (state_specs, round_metric_specs()))
                outs[tag], _ = jax.jit(f)(st, batch0,
                                          jax.random.PRNGKey(0))
        vm, _ = jax.jit(
            lambda s, b, k: federated_round(
                zspecs, s, mlp_loss, b, k, cfgs["fixed"], round_index=0)
        )(encode_state(zspecs, cfgs["fixed"], state), batch0,
          jax.random.PRNGKey(0))
        for p in vm["scores"]:
            a = np.asarray(outs["fixed"]["scores"][p])
            b = np.asarray(outs["sched"]["scores"][p])
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, np.asarray(vm["scores"][p]))

    def test_cosine_anneals_width_up(self, fed_setup):
        zspecs, state, batches, K, E, R = fed_setup
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", downlink="packed4",
                              downlink_schedule="cosine",
                              schedule_b_min=1, schedule_rounds=R)
        st = encode_state(zspecs, cfg, state)
        st1, mets = _fit(zspecs, st, batches, cfg)
        down = np.asarray(mets["downlink_bytes_per_client"], np.float64)
        assert down[0] < down[-1]
        assert (np.diff(down) >= 0).all(), down
        # carry stays at the codec's fixed lane layout throughout
        for p, spec in zspecs.specs.items():
            assert st1["scores"][p].dtype == jnp.uint32
            assert st1["scores"][p].shape == (packed_word_len(spec.n, 4),)

    def test_frontier_beats_constant_u8_on_bytes(self, fed_setup):
        """The acceptance gate: the frontier schedule reaches the u8
        loss neighborhood (within 0.1) at strictly fewer cumulative
        downlink bytes than constant u8."""
        zspecs, state, batches, K, E, R = fed_setup
        base = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.5,
                               aggregate="psum_u32", downlink="u8")
        fr = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.5,
                             aggregate="psum_u32", downlink="u8",
                             downlink_schedule="frontier",
                             schedule_b_min=2)
        _, mu8 = _fit(zspecs, encode_state(zspecs, base, state), batches,
                      base)
        st_fr, mfr = _fit(zspecs, encode_state(zspecs, fr, state),
                          batches, fr)
        cum_u8 = float(np.sum(mu8["downlink_bytes_per_client"]))
        cum_fr = float(np.sum(mfr["downlink_bytes_per_client"]))
        assert cum_fr < cum_u8, (cum_fr, cum_u8)
        lu8 = float(np.asarray(mu8["loss"])[-1])
        lfr = float(np.asarray(mfr["loss"])[-1])
        assert abs(lfr - lu8) < 0.1, (lfr, lu8)
        assert "downlink_b" in st_fr
        b = np.asarray(st_fr["downlink_b"])
        assert b.dtype == np.uint32 and b.shape == (len(zspecs.specs),)
        assert (b >= 2).all() and (b <= 8).all()

    def test_scheduled_bits_meter_matches_lane_padding(self):
        assert scheduled_downlink_bits(65, 4) == 32 * 9
        assert scheduled_downlink_bits(64, 4) == 32 * 8
        traced = jax.jit(
            lambda b: scheduled_downlink_bits(65, b))(jnp.uint32(4))
        assert int(traced) == 32 * 9


# ---------------------------------------------------------------------------
# routing + checkpoint round-trip (satellites 1 & 2)
# ---------------------------------------------------------------------------

class TestRoutingAndCheckpoint:
    def test_infer_raises_on_packed_carry(self, fed_setup):
        zspecs, state, *_ = fed_setup
        cfg = FederatedConfig(downlink="packed4")
        st = encode_state(zspecs, cfg, state)
        with pytest.raises(ValueError):
            infer_downlink(st["scores"])

    def test_sample_weights_needs_tag_for_packed(self, fed_setup):
        zspecs, state, *_ = fed_setup
        cfg = FederatedConfig(downlink="packed4")
        st = encode_state(zspecs, cfg, state)
        key = jax.random.PRNGKey(2)
        with pytest.raises(ValueError):
            sample_weights(zspecs, st, key)  # sniffing is ambiguous
        w = sample_weights(zspecs, st, key, carried="packed4")
        for leaf in jax.tree.leaves(w):
            assert jnp.asarray(leaf).dtype == jnp.float32
        # the WRONG packed tag is rejected by the lane-count check
        # (packed2 lanes are longer), not silently misdecoded
        with pytest.raises(ValueError):
            sample_weights(zspecs, st, key, carried="packed2")

    def test_evaluate_with_carried_tag(self, fed_setup):
        from repro.train import evaluate

        zspecs, state, *_ = fed_setup
        cfg = FederatedConfig(downlink="packed4")
        st = encode_state(zspecs, cfg, state)
        ms, _ = evaluate(zspecs, st, lambda p: 1.0, jax.random.PRNGKey(0),
                         n_samples=2, carried="packed4")
        assert ms == 1.0

    def test_checkpoint_roundtrip_packed_carry_bitwise(self, fed_setup,
                                                       tmp_path):
        from repro.checkpoint import (checkpoint_downlink,
                                      load_checkpoint, save_checkpoint)

        zspecs, state, batches, K, E, R = fed_setup
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", downlink="packed4",
                              downlink_schedule="frontier",
                              schedule_b_min=2)
        st = encode_state(zspecs, cfg, state)
        st1, _ = _fit(zspecs, st, batches, cfg)
        path = str(tmp_path / "packed_carry.npz")
        save_checkpoint(path, st1, downlink="packed4")
        loaded, meta = load_checkpoint(path, st1)
        assert checkpoint_downlink(meta) == "packed4"
        flat1 = jax.tree_util.tree_leaves_with_path(st1)
        flat2 = dict(jax.tree_util.tree_leaves_with_path(loaded))
        for p, leaf in flat1:
            got = flat2[p]
            assert np.asarray(got).dtype == np.asarray(leaf).dtype, p
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(leaf), err_msg=str(p))
        # the restored carry + width vector drive another round as-is
        st2 = encode_state(zspecs, cfg, loaded)  # idempotent pass-through
        assert st2["scores"] is loaded["scores"] or all(
            np.array_equal(np.asarray(st2["scores"][k]),
                           np.asarray(loaded["scores"][k]))
            for k in st2["scores"])
        _fit(zspecs, st2, batches, cfg)

    def test_serve_from_packed_carry(self, fed_setup):
        from repro.serve import make_serve_state, reconstruct_resident

        zspecs, state, *_ = fed_setup
        cfg = FederatedConfig(downlink="packed4")
        st = encode_state(zspecs, cfg, state)
        sstate = make_serve_state(zspecs, st, jax.random.PRNGKey(0),
                                  carried="packed4")
        assert sstate.qbits == 4 and sstate.qpacked
        resident = reconstruct_resident(sstate)
        codec = get_codec("packed4")
        for p, spec in zspecs.specs.items():
            q = codec.wire_words(spec, sstate.words[p])
            z = sample_mask_qhash(q, 4, spec.seed, spec.tensor_id,
                                  sstate.step)
            want = ops.reconstruct(spec, z, impl="ref").reshape(spec.shape)
            np.testing.assert_array_equal(np.asarray(resident[p]),
                                          np.asarray(want))
        # wrong tag rejected
        with pytest.raises(ValueError):
            make_serve_state(zspecs, st, jax.random.PRNGKey(0),
                             carried="packed2")
