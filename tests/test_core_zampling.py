"""Unit + property tests for the Zampling core (Q generation, w = Qz)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import zonotope
from repro.core.qspec import make_qspec, row_indices, row_values
from repro.core.reconstruct import materialize_q, reconstruct_ref
from repro.core.sampling import clip_probs, sample_mask, sample_mask_st
from repro.core.zampling import ZamplingConfig, build_specs, init_state, sample_weights


def spec_small(m=600, c=4.0, d=5, window=64, seed=3, fan_in=20):
    return make_qspec(0, (m,), fan_in, compression=c, d=d, window=window, seed=seed)


class TestQSpec:
    def test_rows_have_exactly_d_distinct_indices(self):
        spec = spec_small()
        idx = np.asarray(row_indices(spec, jnp.arange(spec.m_pad)))
        assert idx.shape == (spec.m_pad, spec.d)
        assert (idx >= 0).all() and (idx < spec.window).all()
        for r in range(0, spec.m_pad, 37):
            assert len(set(idx[r].tolist())) == spec.d  # without replacement

    def test_value_distribution_matches_lemma_2_1(self):
        # q_ij ~ N(0, 6/(d fan_in)): check mean/var over many rows
        spec = make_qspec(0, (4096, 64), 64, compression=8, d=8, seed=1)
        vals = np.asarray(row_values(spec, jnp.arange(20000)))
        sigma2 = 6.0 / (spec.d * spec.fan_in)
        assert abs(vals.mean()) < 3 * math.sqrt(sigma2 / vals.size) * 2 + 1e-3
        np.testing.assert_allclose(vals.var(), sigma2, rtol=0.05)

    def test_determinism_across_calls(self):
        spec = spec_small()
        a = row_values(spec, jnp.arange(100))
        b = row_values(spec, jnp.arange(100))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_different_seeds_decorrelate(self):
        s1, s2 = spec_small(seed=1), spec_small(seed=2)
        v1 = np.asarray(row_values(s1, jnp.arange(5000))).ravel()
        v2 = np.asarray(row_values(s2, jnp.arange(5000))).ravel()
        assert abs(np.corrcoef(v1, v2)[0, 1]) < 0.05

    def test_padding_and_window_accounting(self):
        spec = make_qspec(0, (1000,), 10, compression=3, d=4, window=64)
        assert spec.n == spec.num_windows * spec.window
        assert spec.m_pad >= spec.m
        assert spec.n >= spec.n_raw


class TestReconstruct:
    def test_matches_dense_matmul(self):
        spec = spec_small()
        z = (np.random.RandomState(0).rand(spec.n) < 0.5).astype(np.float32)
        q = np.asarray(materialize_q(spec))
        want = q @ z
        got = np.asarray(reconstruct_ref(spec, jnp.asarray(z))).reshape(-1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_kaiming_he_variance_of_w(self):
        # Lemma 2.1: w_i -> N(0, E[p^2] * 6 / fan_in); E[p^2]=1/3 for U(0,1)
        fan_in = 128
        spec = make_qspec(0, (512, fan_in, 128), fan_in, compression=16,
                          d=16, seed=7)
        p = jax.random.uniform(jax.random.PRNGKey(0), (spec.n,))
        w = np.asarray(reconstruct_ref(spec, p)).ravel()
        np.testing.assert_allclose(w.var(), 2.0 / fan_in, rtol=0.1)
        assert abs(w.mean()) < 0.01

    def test_grad_is_q_transpose(self):
        spec = spec_small(m=300, window=32, d=3)
        z = jnp.asarray(np.random.RandomState(1).rand(spec.n), jnp.float32)
        v = jnp.asarray(np.random.RandomState(2).randn(spec.m), jnp.float32)
        f = lambda z_: jnp.vdot(reconstruct_ref(spec, z_).reshape(-1), v)
        g = jax.grad(f)(z)
        q = np.asarray(materialize_q(spec))
        np.testing.assert_allclose(np.asarray(g), q.T @ np.asarray(v),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    @settings(max_examples=15, deadline=None)
    @given(
        m=st.integers(40, 2000),
        c=st.sampled_from([1.0, 2.0, 8.0, 32.0]),
        d=st.integers(1, 16),
        window=st.sampled_from([32, 128, 512]),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property_reconstruct_equals_dense(self, m, c, d, window, seed):
        spec = make_qspec(0, (m,), 16, compression=c, d=d, window=window,
                          seed=seed)
        z = (np.random.RandomState(seed % 1000).rand(spec.n) < 0.5).astype(
            np.float32
        )
        q = np.asarray(materialize_q(spec))
        got = np.asarray(reconstruct_ref(spec, jnp.asarray(z))).reshape(-1)
        np.testing.assert_allclose(got, q @ z, rtol=1e-4, atol=1e-4)


class TestSampling:
    def test_clip_is_paper_f(self):
        s = jnp.asarray([-1.0, 0.0, 0.3, 1.0, 2.0])
        np.testing.assert_allclose(
            np.asarray(clip_probs(s)), [0.0, 0.0, 0.3, 1.0, 1.0]
        )

    def test_mask_is_binary_and_unbiased(self):
        p = jnp.full((20000,), 0.3)
        z = np.asarray(sample_mask(p, jax.random.PRNGKey(0)))
        assert set(np.unique(z)) <= {0.0, 1.0}
        assert abs(z.mean() - 0.3) < 0.02

    def test_straight_through_gradient(self):
        p = jnp.asarray([0.2, 0.8, 0.5])
        g = jax.grad(lambda p_: sample_mask_st(p_, jax.random.PRNGKey(1)).sum())(p)
        np.testing.assert_allclose(np.asarray(g), 1.0)


class TestZamplingTree:
    def _template(self):
        return {
            "layer0": {"kernel": jnp.zeros((64, 128)), "bias": jnp.zeros((128,))},
            "layer1": {"kernel": jnp.zeros((128, 32))},
            "norm": {"scale": jnp.ones((128,))},
        }

    def test_build_specs_partition(self):
        zs = build_specs(self._template(), ZamplingConfig(compression=8, d=4))
        assert set(zs.specs) == {"layer0/kernel", "layer1/kernel"}
        assert set(zs.dense_paths) == {"layer0/bias", "norm/scale"}
        assert zs.m_total == 64 * 128 + 128 * 32
        assert 4 <= zs.compression <= 8.01

    def test_sample_weights_shapes_and_finite(self):
        tmpl = self._template()
        zs = build_specs(tmpl, ZamplingConfig(compression=4, d=4, window=128))
        state = init_state(jax.random.PRNGKey(0), zs)
        w = sample_weights(zs, state, jax.random.PRNGKey(1))
        assert jax.tree.structure(w) == jax.tree.structure(tmpl)
        for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(tmpl)):
            assert a.shape == b.shape
            assert bool(jnp.isfinite(a).all())

    def test_comm_accounting(self):
        zs = build_specs(self._template(), ZamplingConfig(compression=8))
        bits = zs.comm_bits_per_round(packed=True)
        assert bits["client_up"] == zs.n_total
        assert bits["naive_client_up"] == 32 * zs.m_total
        # the headline: >= ~32x compression on top of the 32x binarization
        assert bits["naive_client_up"] / bits["client_up"] > 100


class TestZonotopeTheory:
    def test_lemma_2_2_nonzero_weights(self):
        # empirical E[nnz(w)] vs m(1 - 2^-d), averaging over p~U and z~Bern(p)
        spec = spec_small(m=2000, c=1.0, d=3, window=2048)
        rng, nnz = np.random.RandomState(0), []
        for t in range(30):
            p = rng.rand(spec.n).astype(np.float32)
            z = (rng.rand(spec.n) < p).astype(np.float32)
            w = np.asarray(reconstruct_ref(spec, jnp.asarray(z)))
            nnz.append((np.abs(w) > 1e-12).sum())
        want = zonotope.expected_nonzero_weights(spec.m, spec.d)
        np.testing.assert_allclose(np.mean(nnz), want, rtol=0.05)

    def test_lemma_2_3_empty_columns(self):
        # fraction of z entries with no influence ~ e^-d for m = n
        spec = make_qspec(0, (4096,), 16, compression=1.0, d=2, window=256,
                          seed=5)
        q = np.asarray(materialize_q(spec))
        frac = (np.abs(q).sum(0) == 0).mean()
        np.testing.assert_allclose(frac, math.exp(-spec.d), atol=0.04)

    def test_prop_2_6_jensen_dimension(self):
        # dim(C_tau) of the average >= average of dims
        rng = np.random.RandomState(0)
        ps = [np.clip(rng.rand(500) + rng.randn(500) * 0.3, 0, 1)
              for _ in range(8)]
        tau = 0.05
        dims = [zonotope.tau_hypercube_dim(p, tau) for p in ps]
        dim_avg = zonotope.tau_hypercube_dim(np.mean(ps, 0), tau)
        assert dim_avg >= np.mean(dims) - 1e-9

    def test_log_volume_finite(self):
        v = zonotope.log_expected_zonotope_volume([64] * 100, d=8)
        assert math.isfinite(v)
