"""Batched multi-client reconstruction: ``reconstruct_batched(spec, Z)``
must be exactly ``jax.vmap(reconstruct)(Z)`` — forward and gradient —
across impls (ref / chunked / pallas / sharded), client counts, and
layouts (chunks>1, shard_count>1).  Plus the bitpack round-trip
property test for the masks the batched round puts on the wire."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.bitpack import pack_mask, packed_len, unpack_mask
from repro.core.qspec import make_qspec
from repro.core.reconstruct import (
    grad_z_batched_ref,
    grad_z_ref,
    materialize_q,
    reconstruct_batched_ref,
)
from repro.kernels import ops
from repro.kernels.qz_reconstruct import (
    qz_reconstruct_batched_bwd,
    qz_reconstruct_batched_fwd,
)

# K=8 rides in the @slow set; {1, 3} cover the degenerate and the
# general case fast.
KS = [1, 3, pytest.param(8, marks=pytest.mark.slow)]


def _mk(shape=(64, 96), c=8.0, d=8, window=256, seed=11, **kw):
    fan = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
    return make_qspec(1, shape, fan, compression=c, d=d, window=window,
                      seed=seed, **kw)


def _z(spec, k, seed=0):
    return jnp.asarray(np.random.RandomState(seed).rand(k, spec.n),
                       jnp.float32)


def _vmap_naive(spec, Z, **kw):
    return jax.vmap(
        lambda z: ops.reconstruct(spec, z, auto_batch=False, **kw)
    )(Z)


@pytest.mark.parametrize("k", KS)
def test_batched_ref_equals_vmap_fwd(k):
    spec = _mk()
    Z = _z(spec, k)
    want = _vmap_naive(spec, Z)
    got = ops.reconstruct_batched(spec, Z)
    assert got.shape == (k, *spec.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", KS)
def test_batched_ref_equals_vmap_grad(k):
    spec = _mk()
    Z = _z(spec, k)
    V = jnp.asarray(np.random.RandomState(1).randn(k, *spec.shape),
                    jnp.float32)

    def loss_b(Z_):
        return jnp.vdot(ops.reconstruct_batched(spec, Z_), V)

    def loss_v(Z_):
        return jnp.vdot(_vmap_naive(spec, Z_), V)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_b)(Z)), np.asarray(jax.grad(loss_v)(Z)),
        rtol=1e-4, atol=1e-4,
    )


def test_large_spec_takes_map_strategy():
    # crosses _BATCH_MAP_THRESHOLD: exercises the lax.map contraction
    from repro.core.reconstruct import _BATCH_MAP_THRESHOLD

    spec = _mk((1200, 300), 16.0, 8, 512, seed=2)
    assert spec.m_pad * spec.d >= _BATCH_MAP_THRESHOLD
    Z = _z(spec, 2)
    want = _vmap_naive(spec, Z)
    np.testing.assert_allclose(
        np.asarray(ops.reconstruct_batched(spec, Z)), np.asarray(want),
        rtol=1e-5, atol=1e-5,
    )
    G = jnp.asarray(np.random.RandomState(3).randn(2, *spec.shape),
                    jnp.float32)
    want_g = jax.vmap(lambda g: grad_z_ref(spec, g))(G)
    np.testing.assert_allclose(
        np.asarray(grad_z_batched_ref(spec, G)), np.asarray(want_g),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("chunks", [3, 8])
@pytest.mark.parametrize("k", [1, 3])
def test_batched_chunked_matches(chunks, k):
    spec = _mk((777,), 2.0, 4, 64, seed=4)
    Z = _z(spec, k, seed=4)
    want = ops.reconstruct_batched(spec, Z, chunks=1)
    got = ops.reconstruct_batched(spec, Z, chunks=chunks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # the backward is chunked too (bounded O(rpc·d + K·rpc) temps)
    V = jnp.asarray(np.random.RandomState(5).randn(k, *spec.shape),
                    jnp.float32)

    def g(c):
        return jax.grad(lambda Z_: jnp.vdot(
            ops.reconstruct_batched(spec, Z_, chunks=c), V))(Z)

    np.testing.assert_allclose(np.asarray(g(chunks)), np.asarray(g(1)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunks", [3, 8])
def test_single_chunked_grad_matches(chunks):
    spec = _mk((777,), 2.0, 4, 64, seed=4)
    z = _z(spec, 1, seed=6)[0]
    v = jnp.asarray(np.random.RandomState(7).randn(*spec.shape),
                    jnp.float32)

    def g(c):
        return jax.grad(lambda z_: jnp.vdot(
            ops.reconstruct(spec, z_, chunks=c, auto_batch=False), v))(z)

    np.testing.assert_allclose(np.asarray(g(chunks)), np.asarray(g(1)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize(
    "shape,a,sc", [((8, 6, 16), 2, 4), ((12, 10), 0, 4), ((64, 48), 1, 16)]
)
@pytest.mark.parametrize("k", [1, 3])
def test_batched_sharding_major_layout(shape, a, sc, k):
    """shard_count>1 specs through the (global) ref path: batched must
    equal the dense Q contraction in natural-row order."""
    spec = make_qspec(0, shape, 16, compression=2.0, d=4, window=32,
                      seed=3, major_axis=a, shard_count=sc)
    assert spec.shard_count == sc
    Z = _z(spec, k, seed=5)
    q = np.asarray(materialize_q(spec))
    want = np.einsum("mn,kn->km", q, np.asarray(Z)).reshape(k, *shape)
    got = np.asarray(reconstruct_batched_ref(spec, Z))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    G = jnp.asarray(np.random.RandomState(6).randn(k, *shape), jnp.float32)
    want_g = np.einsum("mn,km->kn", q, np.asarray(G).reshape(k, -1))
    np.testing.assert_allclose(np.asarray(grad_z_batched_ref(spec, G)),
                               want_g, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k", KS)
def test_batched_pallas_matches_ref(k):
    spec = _mk((300, 20), 8.0, 5, 64, seed=7)
    Z = _z(spec, k, seed=7)
    want = np.asarray(reconstruct_batched_ref(spec, Z)).reshape(k, -1)
    got = np.asarray(qz_reconstruct_batched_fwd(spec, Z, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    G = jnp.asarray(np.random.RandomState(8).randn(k, spec.m), jnp.float32)
    want_g = np.asarray(
        grad_z_batched_ref(spec, G.reshape(k, *spec.shape))
    )
    got_g = np.asarray(qz_reconstruct_batched_bwd(spec, G, interpret=True))
    np.testing.assert_allclose(got_g, want_g, rtol=1e-4, atol=1e-4)


def test_pallas_impl_dispatch_batched():
    spec = _mk((300, 20), 8.0, 5, 64, seed=7)
    Z = _z(spec, 3, seed=9)
    ref = ops.reconstruct_batched(spec, Z, impl="ref")
    got = ops.reconstruct_batched(spec, Z, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("batched", [False, True])
def test_pallas_dispatch_major_axis_moved(batched):
    """major_axis != 0 with shard_count == 1: the pallas kernel emits
    moved-order rows — dispatch must un-move them (fwd) and move the
    cotangent (bwd) exactly like the ref path."""
    spec = make_qspec(1, (12, 10), 16, compression=2.0, d=4, window=32,
                      seed=13, major_axis=1, shard_count=1)
    Z = _z(spec, 2, seed=13)
    V = jnp.asarray(np.random.RandomState(14).randn(2, *spec.shape),
                    jnp.float32)
    if batched:
        fwd = lambda impl: ops.reconstruct_batched(spec, Z, impl=impl)
        grad = lambda impl: jax.grad(lambda Z_: jnp.vdot(
            ops.reconstruct_batched(spec, Z_, impl=impl), V))(Z)
    else:
        fwd = lambda impl: ops.reconstruct(spec, Z[0], impl=impl,
                                           auto_batch=False)
        grad = lambda impl: jax.grad(lambda z_: jnp.vdot(
            ops.reconstruct(spec, z_, impl=impl, auto_batch=False),
            V[0]))(Z[0])
    np.testing.assert_allclose(np.asarray(fwd("pallas")),
                               np.asarray(fwd("ref")),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(grad("pallas")),
                               np.asarray(grad("ref")),
                               rtol=1e-4, atol=1e-4)


def test_vmap_auto_lowers_to_batched(monkeypatch):
    """jax.vmap(reconstruct) must dispatch onto the batched impl (the
    custom_vmap rule), not K replicated single-client reconstructions."""
    spec = _mk(seed=12)  # fresh seed: avoid any cached trace of _mk()
    Z = _z(spec, 4)
    calls = []
    real = ops._fwd_many
    monkeypatch.setattr(
        ops, "_fwd_many",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )
    want = _vmap_naive(spec, Z)
    got = jax.vmap(lambda z: ops.reconstruct(spec, z))(Z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert calls, "batched rule never fired under jax.vmap"


def test_vmap_grad_auto_lowers_to_batched(monkeypatch):
    spec = _mk(seed=15)  # fresh seed: avoid any cached trace
    Z = _z(spec, 4)
    V = jnp.asarray(np.random.RandomState(2).randn(4, *spec.shape),
                    jnp.float32)
    calls = []
    real = ops._bwd_many
    monkeypatch.setattr(
        ops, "_bwd_many",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1],
    )

    def gfun(auto):
        def loss(z, v):
            return jnp.vdot(ops.reconstruct(spec, z, auto_batch=auto), v)

        return jax.vmap(jax.grad(loss))(Z, V)

    np.testing.assert_allclose(np.asarray(gfun(True)),
                               np.asarray(gfun(False)),
                               rtol=1e-4, atol=1e-4)
    assert calls, "batched bwd rule never fired under vmap(grad)"


class TestBitpackRoundTrip:
    @settings(max_examples=12, deadline=None)
    @given(n=st.integers(1, 700), seed=st.integers(0, 10_000))
    def test_pack_unpack_roundtrip(self, n, seed):
        z = (np.random.RandomState(seed).rand(n) < 0.5).astype(np.float32)
        packed = pack_mask(jnp.asarray(z))
        assert packed.shape == (packed_len(n),)
        assert packed.dtype == jnp.uint32
        out = np.asarray(unpack_mask(packed, n))
        np.testing.assert_array_equal(out, z)

    def test_pack_is_32x(self):
        n = 4096
        z = jnp.ones((n,), jnp.float32)
        assert pack_mask(z).size * 32 == n
