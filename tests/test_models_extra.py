"""Deeper model-layer tests: flash-vs-direct attention, grouped scan,
MoE dispatch semantics, CE vocab padding, bitpacking properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import MoEConfig
from repro.core.bitpack import pack_mask, packed_len, unpack_mask
from repro.models.attention import AttnDims, _sdpa, decode_self_attention, init_attn_params, init_cache, self_attention
from repro.models.common import cross_entropy, grouped_scan
from repro.models.flash import blockwise_attention
from repro.models.moe import init_moe_params, moe_block


class TestFlashAttention:
    def _qkv(self, B=2, S=256, H=4, KV=2, hd=16, seed=0):
        rs = np.random.RandomState(seed)
        q = jnp.asarray(rs.randn(B, S, H, hd), jnp.float32)
        k = jnp.asarray(rs.randn(B, S, KV, hd), jnp.float32)
        v = jnp.asarray(rs.randn(B, S, KV, hd), jnp.float32)
        return q, k, v

    def _direct(self, q, k, v, causal=True, window=None):
        B, S, H, hd = q.shape
        idx = jnp.arange(S)
        mask = jnp.zeros((B, 1, S, S), jnp.float32)
        if causal:
            mask = jnp.where(idx[None, :] > idx[:, None], -1e30, mask)
        if window is not None:
            mask = jnp.where(idx[None, :] <= idx[:, None] - window, -1e30,
                             mask)
        return _sdpa(q, k, v, mask, H // k.shape[2])

    @pytest.mark.parametrize("window", [None, 64])
    def test_matches_direct(self, window):
        q, k, v = self._qkv()
        want = self._direct(q, k, v, window=window)
        got = blockwise_attention(q, k, v, causal=True, window=window,
                                  q_chunk=64, k_chunk=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_grad_matches_direct(self):
        q, k, v = self._qkv(S=128)

        def f_flash(q):
            return jnp.sum(blockwise_attention(q, k, v, q_chunk=64,
                                               k_chunk=64) ** 2)

        def f_direct(q):
            return jnp.sum(self._direct(q, k, v) ** 2)

        g1 = jax.grad(f_flash)(q)
        g2 = jax.grad(f_direct)(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-3, atol=1e-3)

    def test_noncausal(self):
        q, k, v = self._qkv(S=128)
        want = _sdpa(q, k, v, None, q.shape[2] // k.shape[2])
        got = blockwise_attention(q, k, v, causal=False, q_chunk=64,
                                  k_chunk=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_cross_lengths(self):
        """Sq != Sk (cross-attention path, seamless 32k prefill)."""
        rs = np.random.RandomState(3)
        q = jnp.asarray(rs.randn(1, 256, 4, 16), jnp.float32)
        k = jnp.asarray(rs.randn(1, 128, 2, 16), jnp.float32)
        v = jnp.asarray(rs.randn(1, 128, 2, 16), jnp.float32)
        want = _sdpa(q, k, v, None, 2)
        got = blockwise_attention(q, k, v, causal=False, q_chunk=128,
                                  k_chunk=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


class TestSWADecode:
    def test_ring_buffer_equals_full_forward(self):
        """Decode with ring-buffer SWA cache == forward with window mask."""
        dims = AttnDims(n_heads=4, n_kv=2, head_dim=16, window=8)
        params = init_attn_params(jax.random.PRNGKey(0), 32, dims,
                                  jnp.float32)
        S = 24
        x = jax.random.normal(jax.random.PRNGKey(1), (1, S, 32), jnp.float32)
        positions = jnp.arange(S)[None]
        full = self_attention(params, x, dims, positions)
        cache = init_cache(1, S, dims, jnp.float32)
        outs = []
        for t in range(S):
            y, cache = decode_self_attention(params, x[:, t:t+1], cache, dims)
            outs.append(y[:, 0])
        dec = jnp.stack(outs, 1)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)
        # ring buffer must be no larger than the window
        assert cache.k.shape[1] == 8


class TestGroupedScan:
    def test_matches_plain_scan_and_grad(self):
        L, D = 16, 8
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.1
        x0 = jax.random.normal(jax.random.PRNGKey(1), (D,))

        def body(x, w):
            return jnp.tanh(w @ x), None

        def f_plain(x0):
            x, _ = jax.lax.scan(body, x0, ws)
            return jnp.sum(x ** 2)

        def f_grouped(x0):
            return jnp.sum(grouped_scan(body, x0, ws, group=4) ** 2)

        np.testing.assert_allclose(f_plain(x0), f_grouped(x0), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(jax.grad(f_plain)(x0)),
            np.asarray(jax.grad(f_grouped)(x0)), rtol=1e-5, atol=1e-6,
        )

    def test_awkward_group_falls_back(self):
        L, D = 7, 4
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.1
        x0 = jnp.ones((D,))

        def body(x, w):
            return jnp.tanh(w @ x), None

        out = grouped_scan(body, x0, ws, group=4)  # 7 % 4 != 0
        plain, _ = jax.lax.scan(body, x0, ws)
        np.testing.assert_allclose(np.asarray(out), np.asarray(plain))


class TestMoE:
    def test_group_locality_preserves_routing(self):
        """With ample capacity, grouped == ungrouped output."""
        cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=16,
                        capacity_factor=8.0)
        params = init_moe_params(jax.random.PRNGKey(0), 8, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 8), jnp.float32)
        out_one, _ = moe_block(params, x, cfg, group_size=128)  # 1 group
        out_four, _ = moe_block(params, x, cfg, group_size=32)  # 4 groups
        np.testing.assert_allclose(np.asarray(out_one), np.asarray(out_four),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_per_token_reference(self):
        cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=16,
                        capacity_factor=8.0)
        D = 8
        params = init_moe_params(jax.random.PRNGKey(0), D, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, D), jnp.float32)
        out, _ = moe_block(params, x, cfg)

        # reference: loop over tokens, run top-k experts densely
        logits = x[0] @ params["router"]
        probs = jax.nn.softmax(logits, -1)
        ref = []
        for t in range(16):
            gv, gi = jax.lax.top_k(probs[t], 2)
            gv = gv / gv.sum()
            acc = jnp.zeros((D,))
            for w, e in zip(np.asarray(gv), np.asarray(gi)):
                h = jax.nn.silu(x[0, t] @ params["gate"][e]) * (
                    x[0, t] @ params["up"][e]
                )
                acc = acc + w * (h @ params["down"][e])
            ref.append(acc)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_capacity_drops_tokens(self):
        cfg = MoEConfig(num_experts=2, top_k=1, d_ff_expert=8,
                        capacity_factor=0.25)
        params = init_moe_params(jax.random.PRNGKey(0), 4, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 4))
        out, aux = moe_block(params, x, cfg)
        assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(aux))


class TestCrossEntropy:
    def test_vocab_padding_equivalence(self):
        rs = np.random.RandomState(0)
        logits = jnp.asarray(rs.randn(4, 8, 10), jnp.float32)
        labels = jnp.asarray(rs.randint(0, 10, (4, 8)), jnp.int32)
        base = cross_entropy(logits, labels)
        padded = jnp.pad(logits, ((0, 0), (0, 0), (0, 6)),
                         constant_values=5.0)  # junk in pad columns
        got = cross_entropy(padded, labels, num_classes=10)
        np.testing.assert_allclose(float(got), float(base), rtol=1e-6)

    def test_matches_naive_softmax_ce(self):
        rs = np.random.RandomState(1)
        logits = jnp.asarray(rs.randn(3, 5, 7), jnp.float32)
        labels = jnp.asarray(rs.randint(0, 7, (3, 5)), jnp.int32)
        want = -jnp.mean(
            jnp.take_along_axis(jax.nn.log_softmax(logits), labels[..., None],
                                -1)
        )
        got = cross_entropy(logits, labels)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


class TestBitpack:
    @settings(max_examples=30, deadline=None)
    @given(n=st.integers(1, 500), seed=st.integers(0, 1000))
    def test_roundtrip(self, n, seed):
        z = (np.random.RandomState(seed).rand(n) < 0.5).astype(np.float32)
        packed = pack_mask(jnp.asarray(z))
        assert packed.shape == (packed_len(n),)
        back = unpack_mask(packed, n)
        np.testing.assert_array_equal(np.asarray(back), z)

    def test_wire_size_is_n_bits(self):
        n = 1024
        z = jnp.ones((n,))
        assert pack_mask(z).size * 32 == n
