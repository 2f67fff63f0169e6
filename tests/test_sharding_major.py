"""Sharding-major layout (QSpec.major_axis / shard_count) consistency:
the distributed reconstruction must be a pure re-layout of the same Q —
validated globally on CPU against materialize_q."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qspec import make_qspec
from repro.core.reconstruct import grad_z_ref, materialize_q, reconstruct_ref

CASES = [
    # (shape, major_axis, shard_count, compression, d, window)
    ((8, 6, 16), 2, 4, 2.0, 4, 32),
    ((12, 10), 0, 4, 4.0, 5, 32),
    ((4, 32, 5), 1, 8, 2.0, 3, 16),
    ((64, 48), 1, 16, 8.0, 8, 64),
]


@pytest.mark.parametrize("shape,a,sc,c,d,window", CASES)
def test_reconstruct_matches_dense_q(shape, a, sc, c, d, window):
    spec = make_qspec(0, shape, 16, compression=c, d=d, window=window,
                      seed=3, major_axis=a, shard_count=sc)
    assert spec.shard_count == sc  # no silent fallback
    z = (np.random.RandomState(0).rand(spec.n) < 0.5).astype(np.float32)
    q = np.asarray(materialize_q(spec))  # natural-order rows
    want = (q @ z).reshape(shape)
    got = np.asarray(reconstruct_ref(spec, jnp.asarray(z)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,a,sc,c,d,window", CASES)
def test_grad_matches_dense_q_transpose(shape, a, sc, c, d, window):
    spec = make_qspec(0, shape, 16, compression=c, d=d, window=window,
                      seed=3, major_axis=a, shard_count=sc)
    g = np.random.RandomState(1).randn(*shape).astype(np.float32)
    q = np.asarray(materialize_q(spec))
    want = q.T @ g.reshape(-1)
    got = np.asarray(grad_z_ref(spec, jnp.asarray(g)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fallback_when_axis_not_divisible():
    spec = make_qspec(0, (7, 10), 7, compression=2, d=3, window=16,
                      major_axis=0, shard_count=4)  # 7 % 4 != 0
    assert spec.shard_count == 1 and spec.major_axis == 0


def test_block_window_locality():
    """Rows of block k must only index block k's windows."""
    from repro.core.qspec import padded_row_window

    spec = make_qspec(0, (64, 48), 16, compression=8.0, d=8, window=64,
                      seed=3, major_axis=1, shard_count=16)
    rp = jnp.arange(spec.m_pad, dtype=jnp.int32)
    win = np.asarray(padded_row_window(spec, rp))
    blk = np.asarray(rp) // spec.m_pad_loc
    assert (win // spec.nw_loc == blk).all()


def _model_mesh(size=4):
    if len(jax.devices()) < size:
        pytest.skip(f"needs {size} devices (conftest forces 4 on CPU)")
    return jax.make_mesh((size,), ("model",))


class TestShardMapPath:
    """The real distributed op (kernels/qz_sharded.py) on a forced
    4-device CPU mesh — single-client and K-stacked, vs dense Q."""

    def _spec(self):
        return make_qspec(0, (8, 6, 16), 16, compression=2.0, d=4,
                          window=32, seed=3, major_axis=2, shard_count=4)

    def test_sharded_reconstruct_matches_dense(self):
        from repro.kernels.qz_sharded import sharded_reconstruct

        spec = self._spec()
        z = jnp.asarray(np.random.RandomState(0).rand(spec.n), jnp.float32)
        q = np.asarray(materialize_q(spec))
        with jax.set_mesh(_model_mesh()):
            got = np.asarray(sharded_reconstruct(spec, z, 4))
        np.testing.assert_allclose(
            got, (q @ np.asarray(z)).reshape(spec.shape), rtol=1e-5,
            atol=1e-5,
        )

    def test_sharded_batched_matches_dense(self):
        from repro.kernels.qz_sharded import (
            sharded_grad_z_batched,
            sharded_reconstruct_batched,
        )

        spec = self._spec()
        k = 3
        Z = jnp.asarray(np.random.RandomState(1).rand(k, spec.n),
                        jnp.float32)
        q = np.asarray(materialize_q(spec))
        with jax.set_mesh(_model_mesh()):
            got = np.asarray(sharded_reconstruct_batched(spec, Z, 4))
        want = np.einsum("mn,kn->km", q, np.asarray(Z)).reshape(
            k, *spec.shape
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        G = jnp.asarray(np.random.RandomState(2).randn(k, *spec.shape),
                        jnp.float32)
        with jax.set_mesh(_model_mesh()):
            got_g = np.asarray(sharded_grad_z_batched(spec, G, 4))
        want_g = np.einsum("mn,km->kn", q, np.asarray(G).reshape(k, -1))
        np.testing.assert_allclose(got_g, want_g, rtol=1e-4, atol=1e-4)

    def test_ops_dispatch_through_mesh(self):
        spec = self._spec()
        from repro.kernels import ops

        Z = jnp.asarray(np.random.RandomState(3).rand(2, spec.n),
                        jnp.float32)
        want = np.asarray(reconstruct_ref(spec, Z[0]))
        with jax.set_mesh(_model_mesh()):
            got = np.asarray(ops.reconstruct(spec, Z[0], model_size=4))
            got_b = np.asarray(
                ops.reconstruct_batched(spec, Z, model_size=4)
            )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_b[0], want, rtol=1e-5, atol=1e-5)


class TestShardedLocalDraw:
    """The fused sharded forward draws only the shard's own windows
    (coords offset by ``sid * n_loc``) — bit-identical to drawing the
    replicated (n,) mask and re-slicing, for the f32 and quantized
    downlink paths, single and K-stacked, and through the public op."""

    def _spec(self):
        return make_qspec(0, (8, 6, 16), 16, compression=2.0, d=4,
                          window=32, seed=3, major_axis=2, shard_count=4)

    def test_local_draw_matches_replicated_draw(self):
        from repro.core.sampling import sample_mask_hash
        from repro.kernels.qz_sharded import (
            sharded_reconstruct,
            sharded_sample_reconstruct,
        )

        spec = self._spec()
        p = jnp.asarray(np.random.RandomState(0).rand(spec.n), jnp.float32)
        step = jnp.uint32(77)
        with jax.set_mesh(_model_mesh()):
            z = sample_mask_hash(p, spec.seed, spec.tensor_id, step)
            want = np.asarray(sharded_reconstruct(spec, z, 4))
            got = np.asarray(sharded_sample_reconstruct(spec, p, step, 4))
        np.testing.assert_array_equal(got, want)

    def test_local_draw_batched_and_quantized(self):
        from repro.core.sampling import sample_mask_hash, sample_mask_qhash
        from repro.kernels.qz_sharded import (
            sharded_reconstruct,
            sharded_reconstruct_batched,
            sharded_sample_reconstruct,
            sharded_sample_reconstruct_batched,
        )

        spec = self._spec()
        k = 5
        Pr = jnp.asarray(np.random.RandomState(1).rand(k, spec.n),
                         jnp.float32)
        steps = jnp.arange(10, 10 + k, dtype=jnp.uint32)
        q = jnp.asarray((np.random.RandomState(2).rand(spec.n) * 255)
                        .astype(np.uint8))
        with jax.set_mesh(_model_mesh()):
            Z = sample_mask_hash(Pr, spec.seed, spec.tensor_id, steps)
            want_b = np.asarray(sharded_reconstruct_batched(spec, Z, 4))
            got_b = np.asarray(
                sharded_sample_reconstruct_batched(spec, Pr, steps, 4))
            zq = sample_mask_qhash(q, 8, spec.seed, spec.tensor_id,
                                   jnp.uint32(77))
            want_q = np.asarray(sharded_reconstruct(spec, zq, 4))
            got_q = np.asarray(sharded_sample_reconstruct(
                spec, q.astype(jnp.uint32), jnp.uint32(77), 4, qbits=8))
        np.testing.assert_array_equal(got_b, want_b)
        np.testing.assert_array_equal(got_q, want_q)

    def test_public_fused_op_uses_local_draw(self):
        from repro.core.sampling import sample_mask_hash
        from repro.kernels import ops
        from repro.kernels.qz_sharded import (
            sharded_reconstruct,
            sharded_reconstruct_batched,
        )

        spec = self._spec()
        Pr = jnp.asarray(np.random.RandomState(3).rand(2, spec.n),
                         jnp.float32)
        steps = jnp.asarray([4, 9], jnp.uint32)
        with jax.set_mesh(_model_mesh()):
            Z = sample_mask_hash(Pr, spec.seed, spec.tensor_id, steps)
            want = np.asarray(sharded_reconstruct(spec, Z[0], 4))
            want_b = np.asarray(sharded_reconstruct_batched(spec, Z, 4))
            got = np.asarray(ops.sample_reconstruct(
                spec, Pr[0], steps[0], model_size=4))
            got_b = np.asarray(ops.sample_reconstruct_batched(
                spec, Pr, steps, model_size=4))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_b, want_b)


def test_autodiff_through_reconstruct_sc():
    spec = make_qspec(0, (8, 6, 16), 16, compression=2.0, d=4, window=32,
                      seed=5, major_axis=2, shard_count=4)
    z = jnp.asarray(np.random.RandomState(2).rand(spec.n), jnp.float32)
    v = jnp.asarray(np.random.RandomState(3).randn(8, 6, 16), jnp.float32)
    g = jax.grad(lambda z_: jnp.vdot(reconstruct_ref(spec, z_), v))(z)
    q = np.asarray(materialize_q(spec))
    np.testing.assert_allclose(np.asarray(g), q.T @ np.asarray(v).reshape(-1),
                               rtol=1e-4, atol=1e-4)
