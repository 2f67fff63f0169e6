"""Downlink codec subsystem (comm.downlink + the quantized draw path).

The codec contract, pinned here:

 - ``f32`` is the IDENTITY oracle: encode/decode pass arrays through
   untouched, so ``downlink='f32'`` rounds are bit-identical to the
   pre-codec protocol (fwd + grad, vmap and 4-device shard_map);
 - ``u8``/``u16`` are EXACT at the draw-word level: the widened
   threshold ``T(q) = floor(q * 2^24 / (2^b - 1))`` is computed
   exactly in uint32, the integer-compare draw
   ``(hash >> 8) < T(q)`` fires with probability exactly
   ``T(q) * 2^-24`` (the decoded probability, exactly representable in
   f32), and it is bit-identical to ``bernoulli_u32`` on that decoded
   value — for every draw word;
 - encode -> decode round-trips within ``2^-b`` (dithered rounding at
   half amplitude + the threshold floor);
 - the encoded scores ARE the round carry: quantized rounds thread
   uint8/uint16 score pytrees through ``federated_round`` /
   ``federated_fit`` / ``sharded_client_update``, with the vmap and
   shard_map paths producing bit-identical encoded states;
 - metering: ``downlink_bytes_*`` / ``downlink_vs_f32`` keys, and the
   analytic ``comm_bits_per_round``'s ``server_down_wire`` == 8x the
   metered ``downlink_bytes_per_client`` per codec;
 - an MNIST-FC smoke run: u16's final loss lands within tolerance of
   the f32 oracle's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm.downlink import (
    codec_for_dtype,
    codec_names,
    get_codec,
)
from repro.comm.metering import (
    downlink_table,
    round_wire_report,
    score_downlink_bytes,
    wire_table,
)
from repro.comm.shardmap import shard_map
from repro.core import (
    FederatedConfig,
    ZamplingConfig,
    build_specs,
    encode_state,
    decode_state,
    init_state,
)
from repro.core.federated import (
    WIRE_METRIC_KEYS,
    federated_round,
    sharded_client_update,
)
from repro.core.hashrng import bernoulli_u32
from repro.core.qspec import make_qspec
from repro.core.sampling import (
    quant_threshold_u24,
    sample_mask_hash,
    sample_mask_qhash,
)
from repro.core.zampling import MaskProgram, infer_downlink, sample_weights
from repro.kernels import ops

CODECS = ("f32", "u16", "u8", "packed4", "packed2")
# per-coordinate-word quantized codecs; the packed sub-byte codecs
# (uint32 lane carrier) have their own suite in test_packed_downlink.py
QUANTIZED = ("u16", "u8")


def _mk(shape=(300, 20), c=8.0, d=5, window=64, seed=7, **kw):
    fan = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
    return make_qspec(1, shape, fan, compression=c, d=d, window=window,
                      seed=seed, **kw)


def _qwords(codec, n, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, 1 << codec.bits, n),
                       codec.wire_dtype)


# ---------------------------------------------------------------------------
# registry + config validation (satellite)
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_registered_codecs(self):
        assert codec_names(include_aliases=False) == sorted(CODECS)
        assert get_codec("f32").bits == 32
        assert get_codec("u16").bits == 16
        assert get_codec("u8").bits == 8

    def test_unknown_codec_raises(self):
        with pytest.raises(ValueError, match="registered"):
            get_codec("u7")

    def test_config_validates_at_construction(self):
        with pytest.raises(ValueError) as ei:
            FederatedConfig(downlink="u7")
        for name in CODECS:
            assert name in str(ei.value)

    @pytest.mark.parametrize("name", CODECS)
    def test_registered_codecs_accepted(self, name):
        assert FederatedConfig(downlink=name).downlink == name

    def test_codec_for_dtype(self):
        assert codec_for_dtype(jnp.float32).name == "f32"
        assert codec_for_dtype(jnp.uint8).name == "u8"
        assert codec_for_dtype(jnp.uint16).name == "u16"
        with pytest.raises(ValueError, match="registered"):
            codec_for_dtype(jnp.int64)


# ---------------------------------------------------------------------------
# the widened threshold: exact integer math
# ---------------------------------------------------------------------------

class TestThreshold:
    @pytest.mark.parametrize("bits", [8, 16])
    def test_exact_floor(self, bits):
        """T(q) == floor(q * 2^24 / (2^b - 1)) for every (u8) / a dense
        sample + boundaries (u16) of the wire alphabet — exact python
        bigint arithmetic as the oracle."""
        S = (1 << bits) - 1
        if bits == 8:
            qs = np.arange(S + 1)
        else:
            rng = np.random.RandomState(0)
            qs = np.unique(np.concatenate([
                np.arange(0, 300), np.array([S - 2, S - 1, S]),
                rng.randint(0, S + 1, 4000),
            ]))
        T = np.asarray(quant_threshold_u24(jnp.asarray(qs, jnp.uint32),
                                           bits))
        want = np.array([(int(q) * (1 << 24)) // S for q in qs],
                        np.uint32)
        np.testing.assert_array_equal(T, want)
        assert T[0] == 0
        assert int(quant_threshold_u24(jnp.uint32(S), bits)) == 1 << 24

    def test_invalid_bits_raises(self):
        with pytest.raises(ValueError, match="bits"):
            quant_threshold_u24(jnp.uint32(1), 32)

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_decode_is_threshold_over_2_24(self, name):
        """decode(q) == T(q) * 2^-24 exactly in f32, within 2^-24 of
        the ideal q / (2^b - 1)."""
        codec = get_codec(name)
        S = (1 << codec.bits) - 1
        q = _qwords(codec, 4096, seed=1)
        spec = _mk()
        phat = np.asarray(codec.decode(spec, q))
        T = np.asarray(quant_threshold_u24(q, codec.bits))
        np.testing.assert_array_equal(phat,
                                      T.astype(np.float64) * 2.0 ** -24)
        ideal = np.asarray(q).astype(np.float64) / S
        assert np.abs(phat - ideal).max() <= 2.0 ** -24
        assert phat.min() >= 0.0 and phat.max() <= 1.0


# ---------------------------------------------------------------------------
# the quantized draw: exactly unbiased at the draw-word level
# ---------------------------------------------------------------------------

class TestQuantizedDraw:
    @pytest.mark.parametrize("name", QUANTIZED)
    def test_bit_identical_to_f32_draw_on_decoded(self, name):
        """The integer compare == bernoulli_u32 on the decoded
        probability, bit for bit, across steps and coordinates."""
        codec = get_codec(name)
        spec = _mk()
        q = _qwords(codec, spec.n, seed=2)
        phat = codec.decode(spec, q)
        for step in (0, 7, 123456789):
            a = np.asarray(sample_mask_qhash(q, codec.bits, spec.seed,
                                             spec.tensor_id,
                                             jnp.uint32(step)))
            b = np.asarray(sample_mask_hash(phat, spec.seed,
                                            spec.tensor_id,
                                            jnp.uint32(step)))
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("bits", [8, 16])
    def test_every_draw_word_at_the_boundary(self, bits):
        """Exactness for EVERY draw word, not just hash samples: sweep
        v over the threshold boundary — the compare must flip exactly
        at v == T, matching the f32 path's float compare (so the count
        of firing words is exactly T, i.e. P(z=1) == T * 2^-24)."""
        S = (1 << bits) - 1
        for q in (0, 1, S // 3, S // 2, S - 1, S):
            T = int(quant_threshold_u24(jnp.uint32(q), bits))
            phat = np.float32(T * 2.0 ** -24)
            vs = np.unique(np.clip(
                np.array([0, T - 2, T - 1, T, T + 1, (1 << 24) - 1]),
                0, (1 << 24) - 1,
            ))
            u = jnp.asarray((vs.astype(np.uint64) << 8) | 0xAB, jnp.uint32)
            int_draw = (vs < T)
            f32_draw = np.asarray(bernoulli_u32(u, phat)).astype(bool)
            np.testing.assert_array_equal(int_draw, f32_draw, err_msg=str(q))

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_endpoints_exact(self, name):
        codec = get_codec(name)
        S = (1 << codec.bits) - 1
        zeros = jnp.zeros((512,), codec.wire_dtype)
        ones = jnp.full((512,), S, codec.wire_dtype)
        assert np.asarray(sample_mask_qhash(zeros, codec.bits, 3, 1,
                                            jnp.uint32(5))).sum() == 0
        assert np.asarray(sample_mask_qhash(ones, codec.bits, 3, 1,
                                            jnp.uint32(5))).sum() == 512

    def test_empirical_mean_matches_analytic(self):
        """Frequency over many draw words ~ T * 2^-24 (CLT bound)."""
        codec = get_codec("u8")
        q = jnp.full((200_000,), 85, codec.wire_dtype)  # ~ 1/3
        p = int(quant_threshold_u24(jnp.uint32(85), 8)) * 2.0 ** -24
        z = np.asarray(sample_mask_qhash(q, 8, 3, 1, jnp.uint32(11)))
        sigma = (p * (1 - p) / z.size) ** 0.5
        assert abs(z.mean() - p) < 5 * sigma


# ---------------------------------------------------------------------------
# encode: shared-stream dither, round-trip error
# ---------------------------------------------------------------------------

class TestEncode:
    @pytest.mark.parametrize("name", QUANTIZED)
    def test_roundtrip_error_within_2_pow_b(self, name):
        codec = get_codec(name)
        spec = _mk()
        rng = np.random.RandomState(3)
        p = jnp.asarray(rng.rand(20_000), jnp.float32)
        q = codec.encode(spec, p, jnp.uint32(5))
        assert q.dtype == jnp.dtype(codec.wire_dtype)
        err = np.abs(np.asarray(codec.decode(spec, q), np.float64)
                     - np.asarray(p, np.float64))
        assert err.max() <= 2.0 ** -codec.bits, err.max()

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_deterministic_per_word(self, name):
        """Same (spec, word) -> identical encoding (the shard_map
        shards' agreement); different words dither differently."""
        codec = get_codec(name)
        spec = _mk()
        p = jnp.asarray(np.random.RandomState(4).rand(spec.n), jnp.float32)
        a = np.asarray(codec.encode(spec, p, jnp.uint32(9)))
        b = np.asarray(codec.encode(spec, p, jnp.uint32(9)))
        np.testing.assert_array_equal(a, b)
        c = np.asarray(codec.encode(spec, p, jnp.uint32(10)))
        assert (a != c).any()

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_clips_and_keeps_endpoints(self, name):
        codec = get_codec(name)
        spec = _mk()
        S = (1 << codec.bits) - 1
        p = jnp.asarray([-2.0, 0.0, 1.0, 3.0], jnp.float32)
        q = np.asarray(codec.encode(spec, p, jnp.uint32(0)))
        np.testing.assert_array_equal(q, [0, 0, S, S])
        dec = np.asarray(codec.decode(spec, jnp.asarray(q,
                                                        codec.wire_dtype)))
        np.testing.assert_array_equal(dec, [0.0, 0.0, 1.0, 1.0])

    def test_f32_codec_is_identity(self):
        codec = get_codec("f32")
        spec = _mk()
        p = jnp.asarray(np.random.RandomState(5).rand(spec.n), jnp.float32)
        assert codec.encode(spec, p, jnp.uint32(3)) is p
        assert codec.decode(spec, p) is p


# ---------------------------------------------------------------------------
# fused kernels accept the quantized operand (tentpole)
# ---------------------------------------------------------------------------

class TestFusedQuantized:
    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    @pytest.mark.parametrize("name", QUANTIZED)
    def test_single_matches_composed(self, impl, name):
        codec = get_codec(name)
        spec = _mk()
        q = _qwords(codec, spec.n, seed=6)
        step = jnp.uint32(42)
        z = sample_mask_hash(codec.decode(spec, q), spec.seed,
                             spec.tensor_id, step)
        want = np.asarray(ops.reconstruct(spec, z, impl=impl,
                                          auto_batch=False))
        got = np.asarray(ops.sample_reconstruct(spec, q, step,
                                                qbits=codec.bits,
                                                impl=impl))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_batched_and_vmap_match_composed(self, impl):
        codec = get_codec("u8")
        spec = _mk()
        rng = np.random.RandomState(7)
        Q = jnp.asarray(rng.randint(0, 256, (5, spec.n)), jnp.uint8)
        steps = jnp.arange(5, dtype=jnp.uint32) + 3
        Z = sample_mask_hash(codec.decode(spec, Q), spec.seed,
                             spec.tensor_id, steps)
        want = np.asarray(ops.reconstruct_batched(spec, Z, impl=impl))
        got = np.asarray(ops.sample_reconstruct_batched(
            spec, Q, steps, qbits=8, impl=impl))
        np.testing.assert_array_equal(got, want)
        got_v = np.asarray(jax.vmap(
            lambda q_, s_: ops.sample_reconstruct(spec, q_, s_, qbits=8,
                                                  impl=impl)
        )(Q, steps))
        np.testing.assert_array_equal(got_v, want)

    def test_chunked_matches(self):
        codec = get_codec("u16")
        spec = _mk((777,), 2.0, 4, 64, seed=4)
        q = _qwords(codec, spec.n, seed=8)
        step = jnp.uint32(9)
        want = np.asarray(ops.sample_reconstruct(spec, q, step, qbits=16))
        got = np.asarray(ops.sample_reconstruct(spec, q, step, qbits=16,
                                                chunks=4))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_no_f32_score_slab_in_quantized_pallas_jaxpr(self):
        """The quantized fused path must not materialize an (K, n) f32
        probability slab — the operand stays integer until the
        in-block draw."""
        from test_fused import _eqn_out_shapes

        spec = _mk()
        k = 6
        Q = jnp.asarray(np.random.RandomState(9).randint(
            0, 256, (k, spec.n)), jnp.uint8)
        steps = jnp.arange(k, dtype=jnp.uint32)
        jaxpr = jax.make_jaxpr(
            lambda Q_: ops.sample_reconstruct_batched(spec, Q_, steps,
                                                      qbits=8,
                                                      impl="pallas")
        )(Q)
        shapes = _eqn_out_shapes(jaxpr.jaxpr, [])
        assert ((k, spec.n), "float32") not in shapes


# ---------------------------------------------------------------------------
# MaskProgram: drawing straight from the encoded broadcast
# ---------------------------------------------------------------------------

class TestMaskProgramWire:
    def _zsetup(self):
        template = {
            "l0": {"kernel": jnp.zeros((64, 128))},
            "l1": {"kernel": jnp.zeros((128, 32))},
        }
        zspecs = build_specs(template, ZamplingConfig(
            compression=4, d=4, window=128, min_size=256))
        state = init_state(jax.random.PRNGKey(0), zspecs)
        return zspecs, state

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_weights_from_wire_fused_equals_composed(self, name):
        zspecs, state = self._zsetup()
        cfg = FederatedConfig(downlink=name)
        wire = encode_state(zspecs, cfg, state)["scores"]
        step = jnp.uint32(17)
        w_f = MaskProgram(zspecs, fused=True, downlink=name)\
            .weights_from_wire(wire, state["dense"], step)
        w_c = MaskProgram(zspecs, fused=False, downlink=name)\
            .weights_from_wire(wire, state["dense"], step)
        for a, b in zip(jax.tree.leaves(w_f), jax.tree.leaves(w_c)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_wire_draw_equals_decoded_draw(self, name):
        """masks_from_wire == masks on the decoded f32 state (exact)."""
        zspecs, state = self._zsetup()
        cfg = FederatedConfig(downlink=name)
        encoded = encode_state(zspecs, cfg, state)
        decoded = decode_state(zspecs, cfg, encoded)
        step = jnp.uint32(3)
        prog = MaskProgram(zspecs, downlink=name)
        m_wire = prog.masks_from_wire(encoded["scores"], step)
        m_f32 = MaskProgram(zspecs).masks(decoded["scores"], step)
        for p in m_wire:
            np.testing.assert_array_equal(np.asarray(m_wire[p]),
                                          np.asarray(m_f32[p]))

    def test_discretize_from_wire_is_threshold_compare(self):
        zspecs, state = self._zsetup()
        cfg = FederatedConfig(downlink="u8", mode="discretize")
        encoded = encode_state(zspecs, cfg, state)
        decoded = decode_state(zspecs, cfg, encoded)
        prog = MaskProgram(zspecs, mode="discretize", downlink="u8")
        m_wire = prog.masks_from_wire(encoded["scores"], jnp.uint32(0))
        m_ref = MaskProgram(zspecs, mode="discretize").masks(
            decoded["scores"], jnp.uint32(0))
        for p in m_wire:
            np.testing.assert_array_equal(np.asarray(m_wire[p]),
                                          np.asarray(m_ref[p]))

    def test_sample_weights_infers_codec_from_dtype(self):
        from repro.core.sampling import as_word

        zspecs, state = self._zsetup()
        cfg = FederatedConfig(downlink="u16")
        encoded = encode_state(zspecs, cfg, state)
        assert infer_downlink(encoded["scores"]) == "u16"
        assert infer_downlink(state["scores"]) == "f32"
        key = jax.random.PRNGKey(2)
        w_auto = sample_weights(zspecs, encoded, key)
        w_wire = MaskProgram(zspecs, downlink="u16").weights_from_wire(
            encoded["scores"], encoded["dense"], as_word(key))
        for a, b in zip(jax.tree.leaves(w_auto), jax.tree.leaves(w_wire)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wrong_dtype_raises(self):
        zspecs, state = self._zsetup()
        prog = MaskProgram(zspecs, downlink="u8")
        with pytest.raises(ValueError, match="encode the state"):
            prog.decode_scores(state["scores"])  # f32 leaves into u8

    def test_sample_weights_rejects_mismatched_override(self):
        """An explicit downlink that contradicts the state's leaf
        dtypes must raise — treating u8 wire words as f32 scores would
        silently clip them all to p=1."""
        zspecs, state = self._zsetup()
        encoded = encode_state(zspecs, FederatedConfig(downlink="u8"),
                               state)
        key = jax.random.PRNGKey(4)
        with pytest.raises(ValueError, match="does not match"):
            sample_weights(zspecs, encoded, key, downlink="f32")
        with pytest.raises(ValueError, match="does not match"):
            sample_weights(zspecs, state, key, downlink="u8")
        # the agreeing override still works and equals the inferred path
        w_a = sample_weights(zspecs, encoded, key, downlink="u8")
        w_b = sample_weights(zspecs, encoded, key)
        for a, b in zip(jax.tree.leaves(w_a), jax.tree.leaves(w_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# federated rounds: the encoded scores ARE the carry
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_setup():
    from repro.data import client_batch_stream, iid_client_split, make_teacher_dataset
    from repro.models.mlp import SMALL_DIMS, init_mlp_params

    ds = make_teacher_dataset(n_train=600, n_test=100, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=2.0, d=5, window=128, min_size=256))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    K, E = 4, 2
    clients = iid_client_split(ds, K)
    stream = client_batch_stream(clients, 32, E, seed=0)
    xs, ys = next(stream)
    batch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
    return zspecs, state, batch, stream, K, E


def _round(zspecs, state, batch, cfg, key=0, rid=0):
    from repro.models.mlp import mlp_loss

    return jax.jit(
        lambda s, b, k: federated_round(zspecs, s, mlp_loss, b, k, cfg,
                                        round_index=rid)
    )(state, batch, jax.random.PRNGKey(key))


class TestFederatedRounds:
    def test_f32_codec_bit_identical_to_default(self, fed_setup):
        """downlink='f32' is the identity oracle: same scores (exact),
        same dense grads, as the default config — on every uplink."""
        zspecs, state, batch, _, K, E = fed_setup
        for agg in ("mean_f32", "psum_u32"):
            base, _ = _round(zspecs, state, batch, FederatedConfig(
                num_clients=K, local_steps=E, local_lr=0.1, aggregate=agg))
            got, _ = _round(zspecs, state, batch, FederatedConfig(
                num_clients=K, local_steps=E, local_lr=0.1, aggregate=agg,
                downlink="f32"))
            for p in base["scores"]:
                np.testing.assert_array_equal(
                    np.asarray(base["scores"][p]),
                    np.asarray(got["scores"][p]))
            for p in base["dense"]:
                np.testing.assert_array_equal(
                    np.asarray(base["dense"][p]),
                    np.asarray(got["dense"][p]))

    @pytest.mark.parametrize("name", QUANTIZED)
    def test_quantized_round_carries_wire_dtype(self, fed_setup, name):
        zspecs, state, batch, _, K, E = fed_setup
        codec = get_codec(name)
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", downlink=name)
        st = encode_state(zspecs, cfg, state)
        st1, met = _round(zspecs, st, batch, cfg)
        for p, spec in zspecs.specs.items():
            assert st1["scores"][p].dtype == jnp.dtype(codec.wire_dtype)
            assert st1["scores"][p].shape == (spec.n,)
        # round metrics meter the configured codec exactly (f32 cast)
        rep = round_wire_report(zspecs, "psum_u32", K, downlink=name)
        assert np.isclose(float(met["downlink_bytes_per_client"]),
                          rep["downlink_bytes_per_client"], rtol=1e-6)
        assert np.isclose(float(met["downlink_bytes_round"]),
                          rep["downlink_bytes_round"], rtol=1e-6)

    def test_quantized_agnostic_to_uplink_transport(self, fed_setup):
        """With a fixed codec the uplink strategies stay bit-exact
        against each other (the encode sees identical aggregates)."""
        zspecs, state, batch, _, K, E = fed_setup
        outs = {}
        for agg in ("mean_f32", "psum_u32", "allgather_packed"):
            cfg = FederatedConfig(num_clients=K, local_steps=E,
                                  local_lr=0.1, aggregate=agg,
                                  downlink="u8")
            st = encode_state(zspecs, cfg, state)
            st1, _ = _round(zspecs, st, batch, cfg)
            outs[agg] = jax.tree.map(np.asarray, st1["scores"])
        for agg in ("psum_u32", "allgather_packed"):
            for p in outs["mean_f32"]:
                np.testing.assert_array_equal(outs["mean_f32"][p],
                                              outs[agg][p])

    def test_encode_state_idempotent_and_guards_cross_codec(self, fed_setup):
        """Re-encoding an already-encoded carry must be a no-op (a
        second pass would reinterpret wire words as f32 scores and
        saturate them to the top code); encoding into a DIFFERENT
        codec raises instead of silently corrupting."""
        zspecs, state, _, _, K, E = fed_setup
        cfg8 = FederatedConfig(num_clients=K, local_steps=E,
                               downlink="u8")
        st8 = encode_state(zspecs, cfg8, state)
        again = encode_state(zspecs, cfg8, st8)
        for p in st8["scores"]:
            np.testing.assert_array_equal(np.asarray(st8["scores"][p]),
                                          np.asarray(again["scores"][p]))
        cfg16 = FederatedConfig(num_clients=K, local_steps=E,
                                downlink="u16")
        with pytest.raises(ValueError, match="already encoded"):
            encode_state(zspecs, cfg16, st8)
        with pytest.raises(ValueError, match="already encoded"):
            encode_state(zspecs, FederatedConfig(num_clients=K,
                                                 local_steps=E), st8)

    def test_float_state_into_quantized_round_raises(self, fed_setup):
        zspecs, state, batch, _, K, E = fed_setup
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              downlink="u8")
        with pytest.raises(ValueError, match="encode the state"):
            _round(zspecs, state, batch, cfg)

    def test_fit_matches_sequential_rounds_u8(self, fed_setup):
        """The scan driver threads the encoded carry: fit over R rounds
        == R sequential rounds, bit for bit, on the u8 codec."""
        from repro.models.mlp import mlp_loss
        from repro.train import federated_fit

        zspecs, state, _, stream, K, E = fed_setup
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", downlink="u8")
        st0 = encode_state(zspecs, cfg, state)
        R = 3
        xs, ys = zip(*(next(stream) for _ in range(R)))
        batches = {"x": jnp.asarray(np.stack(xs)),
                   "y": jnp.asarray(np.stack(ys))}
        key = jax.random.PRNGKey(7)
        st_fit, mets = jax.jit(
            lambda s, b, k: federated_fit(zspecs, s, mlp_loss, b, k, cfg)
        )(st0, batches, key)
        assert mets["loss"].shape == (R,)
        st_seq = st0
        for r, sub in enumerate(jax.random.split(key, R)):
            b = jax.tree.map(lambda x, r=r: x[r], batches)
            st_seq, _ = jax.jit(
                lambda s, b_, k, r_=jnp.uint32(r): federated_round(
                    zspecs, s, mlp_loss, b_, k, cfg, round_index=r_)
            )(st_seq, b, sub)
        for p in st_fit["scores"]:
            np.testing.assert_array_equal(
                np.asarray(st_fit["scores"][p]),
                np.asarray(st_seq["scores"][p]))

    def test_sharded_round_bit_identical_to_vmap_u8(self, fed_setup):
        """The shard_map path re-encodes the replicated aggregate with
        the shared dither word: encoded carry == the vmap path's,
        bit for bit."""
        from repro.models.mlp import mlp_loss

        mesh = data_mesh_or_skip(4)
        zspecs, state, batch, _, K, E = fed_setup
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", downlink="u8")
        st = encode_state(zspecs, cfg, state)
        want, _ = _round(zspecs, st, batch, cfg)
        state_specs = jax.tree.map(lambda _: P(), st)
        met_specs = round_metric_specs()

        def body(s, b, k):
            b = jax.tree.map(lambda x: x[0], b)
            return sharded_client_update(zspecs, s, mlp_loss, b, k, cfg)

        with jax.set_mesh(mesh):
            f = shard_map(body, ("data",),
                          (state_specs, P("data"), P()),
                          (state_specs, met_specs))
            got, _ = jax.jit(f)(st, batch, jax.random.PRNGKey(0))
        for p in want["scores"]:
            assert got["scores"][p].dtype == jnp.uint8
            np.testing.assert_array_equal(np.asarray(want["scores"][p]),
                                          np.asarray(got["scores"][p]))

    def test_evaluate_on_encoded_carry(self, fed_setup):
        """train.local.evaluate consumes the quantized carry directly
        (sample_weights infers the codec from the leaf dtype)."""
        from repro.train import evaluate

        zspecs, state, batch, _, K, E = fed_setup
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              downlink="u16")
        st = encode_state(zspecs, cfg, state)
        st1, _ = _round(zspecs, st, batch, cfg)
        metric = jax.jit(
            lambda params: sum(jnp.sum(l * l) for l in
                               jax.tree.leaves(params)))
        m, s = evaluate(zspecs, st1, metric, jax.random.PRNGKey(3),
                        n_samples=3)
        assert np.isfinite(m)


# ---------------------------------------------------------------------------
# metering: bidirectional wire accounting
# ---------------------------------------------------------------------------

class TestDownlinkMetering:
    def _zspecs(self):
        # all leaves reparametrized (no dense): the downlink ratio is
        # exactly bits/32
        template = {
            "l0": {"kernel": jnp.zeros((64, 128))},
            "l1": {"kernel": jnp.zeros((128, 32))},
        }
        return build_specs(template, ZamplingConfig(
            compression=4, d=4, window=128, min_size=256))

    def test_downlink_keys_and_exact_ratio(self):
        zspecs = self._zspecs()
        K = 10
        f32 = round_wire_report(zspecs, "psum_u32", K, downlink="f32")
        u8 = round_wire_report(zspecs, "psum_u32", K, downlink="u8")
        u16 = round_wire_report(zspecs, "psum_u32", K, downlink="u16")
        n = zspecs.n_total
        assert f32["downlink_bytes_per_client"] == 4 * n
        assert u16["downlink_bytes_per_client"] == 2 * n
        assert u8["downlink_bytes_per_client"] == 1 * n
        assert u8["downlink_vs_f32"] == 0.25
        assert u16["downlink_vs_f32"] == 0.5
        for rep in (f32, u8):
            assert rep["downlink_bytes_round"] == (
                K * rep["downlink_bytes_per_client"])
        # the acceptance claim: u8 drops the metered downlink >= 4x
        assert (f32["downlink_bytes_per_client"]
                / u8["downlink_bytes_per_client"]) >= 4.0

    def test_wire_metric_keys_cover_downlink(self):
        assert "downlink_bytes_per_client" in WIRE_METRIC_KEYS
        assert "downlink_bytes_round" in WIRE_METRIC_KEYS
        zspecs = self._zspecs()
        rep = round_wire_report(zspecs, "mean", 4, downlink="u8")
        for k in WIRE_METRIC_KEYS:
            assert k in rep

    def test_comm_bits_cross_check_per_codec(self):
        """server_down_wire == 8 x metered downlink bytes, per codec
        (the analytic/exact cross-check, downlink leg)."""
        zspecs = self._zspecs()
        for name in CODECS:
            bits = zspecs.comm_bits_per_round(packed=True, downlink=name)
            rep = round_wire_report(zspecs, "psum_u32", 10, downlink=name)
            assert bits["server_down_wire"] == 8 * rep[
                "downlink_bytes_per_client"], name
            assert bits["server_down"] == get_codec(name).bits * (
                zspecs.n_total)

    def test_tables_carry_downlink_columns(self):
        zspecs = self._zspecs()
        rows = wire_table(zspecs, 4, downlink="u8")
        for r in rows:
            assert r["downlink"] == "u8"
            assert r["downlink_bytes_per_client"] == zspecs.n_total
        down = downlink_table(zspecs, 4)
        assert {r["codec"] for r in down} == set(CODECS)
        by = {r["codec"]: r for r in down}
        assert by["f32"]["downlink_vs_f32"] == 1.0
        assert by["u8"]["downlink_bytes_per_client"] < by["u16"][
            "downlink_bytes_per_client"]

    def test_score_downlink_bytes(self):
        assert score_downlink_bytes(get_codec("f32"), 1000) == 4000
        assert score_downlink_bytes(get_codec("u16"), 1000) == 2000
        assert score_downlink_bytes(get_codec("u8"), 1000) == 1000
        # odd bit totals round up to whole bytes
        assert score_downlink_bytes(get_codec("u8"), 3) == 3


# ---------------------------------------------------------------------------
# MNIST-FC smoke: u16 within tolerance of the f32 oracle
# ---------------------------------------------------------------------------

def test_mnistfc_u16_loss_close_to_f32(fed_setup):
    """A short federated fit per codec on the MNIST-FC stand-in: the
    u16 broadcast's rounding noise must not derail training — final
    loss within tolerance of the f32 oracle, and both decrease."""
    from repro.models.mlp import mlp_loss
    from repro.train import federated_fit

    zspecs, state, _, stream, K, E = fed_setup
    R = 5
    xs, ys = zip(*(next(stream) for _ in range(R)))
    batches = {"x": jnp.asarray(np.stack(xs)),
               "y": jnp.asarray(np.stack(ys))}
    losses = {}
    for name in ("f32", "u16"):
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.5,
                              aggregate="psum_u32", downlink=name)
        st = encode_state(zspecs, cfg, state)
        _, mets = jax.jit(
            lambda s, b, k, cfg=cfg: federated_fit(zspecs, s, mlp_loss,
                                                   b, k, cfg)
        )(st, batches, jax.random.PRNGKey(0))
        losses[name] = np.asarray(mets["loss"])
    for name, curve in losses.items():
        assert np.isfinite(curve).all(), name
        assert curve[-1] < curve[0], (name, curve)
    assert abs(losses["u16"][-1] - losses["f32"][-1]) < 0.1, losses
