"""Fused mask lifecycle: ``w = Q·Bern(f(s))`` as one op, masks as
uint32 lanes end-to-end.

The bit-exactness contract: fused ≡ composed (sample -> reconstruct ->
pack) to EXACT equality — forward and gradient — on ref and
interpret-mode Pallas, single-client, vmap-batched (K ∈ {1, 10, 32}),
and the forced 4-device shard_map mesh; plus the architectural claim
that no (K, n) f32 mask array appears in the fused Pallas path's jaxpr.

Satellites covered here: ``set_default_impl`` validation and the
``REPRO_RECONSTRUCT_IMPL`` env override; the analytic-vs-exact wire
accounting cross-check (``ZamplingSpecs.comm_bits_per_round`` vs
``comm.metering.round_wire_report``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm.bitpack import pack_mask, packed_len
from repro.comm.metering import round_wire_report
from repro.comm.shardmap import shard_map
from repro.core import FederatedConfig, ZamplingConfig, build_specs, init_state
from repro.core.federated import federated_round, local_update, sharded_client_update
from repro.core.qspec import make_qspec
from repro.core.sampling import clip_probs, fold_word, mask_u32, sample_mask_hash
from repro.core.zampling import MaskProgram, sample_weights
from repro.kernels import ops

STRATEGIES = ("mean_f32", "psum_u32", "allgather_packed")
KS = [1, 10, 32]


def _mk(shape=(300, 20), c=8.0, d=5, window=64, seed=7, **kw):
    fan = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
    return make_qspec(1, shape, fan, compression=c, d=d, window=window,
                      seed=seed, **kw)


def _probs(spec, k=None, seed=0):
    rng = np.random.RandomState(seed)
    shape = (spec.n,) if k is None else (k, spec.n)
    return jnp.asarray(rng.rand(*shape), jnp.float32)


def _composed_fwd(spec, p, step, impl):
    z = sample_mask_hash(p, spec.seed, spec.tensor_id, step)
    if p.ndim == 2:
        return ops.reconstruct_batched(spec, z, impl=impl)
    return ops.reconstruct(spec, z, impl=impl, auto_batch=False)


# ---------------------------------------------------------------------------
# fused == composed: forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_equals_composed_single(impl):
    spec = _mk()
    p = _probs(spec)
    step = jnp.uint32(42)
    want = np.asarray(_composed_fwd(spec, p, step, impl))
    got = np.asarray(ops.sample_reconstruct(spec, p, step, impl=impl))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("k", KS)
def test_fused_equals_composed_batched(impl, k):
    spec = _mk()
    P_ = _probs(spec, k)
    steps = jnp.arange(k, dtype=jnp.uint32) + 7
    want = np.asarray(_composed_fwd(spec, P_, steps, impl))
    got = np.asarray(ops.sample_reconstruct_batched(spec, P_, steps,
                                                    impl=impl))
    np.testing.assert_array_equal(got, want)
    # jax.vmap over (p, step) must hit the same batched fused impl
    got_v = np.asarray(jax.vmap(
        lambda p_, s_: ops.sample_reconstruct(spec, p_, s_, impl=impl)
    )(P_, steps))
    np.testing.assert_array_equal(got_v, got)


@pytest.mark.parametrize("chunks", [3, 8])
def test_fused_chunked_matches(chunks):
    spec = _mk((777,), 2.0, 4, 64, seed=4)
    p = _probs(spec, seed=4)
    step = jnp.uint32(9)
    want = np.asarray(ops.sample_reconstruct(spec, p, step, chunks=1))
    got = np.asarray(ops.sample_reconstruct(spec, p, step, chunks=chunks))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fused == composed: gradient (straight-through through the clip gate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_grad_equals_composed_single(impl):
    spec = _mk()
    rng = np.random.RandomState(3)
    s = jnp.asarray(rng.randn(spec.n) * 0.7 + 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(*spec.shape), jnp.float32)
    step = jnp.uint32(11)

    def loss_fused(s_):
        return jnp.vdot(
            ops.sample_reconstruct(spec, clip_probs(s_), step, impl=impl), v
        )

    def loss_comp(s_):
        p = clip_probs(s_)
        z = sample_mask_hash(p, spec.seed, spec.tensor_id, step)
        z_st = p + jax.lax.stop_gradient(z - p)
        return jnp.vdot(
            ops.reconstruct(spec, z_st, impl=impl, auto_batch=False), v
        )

    np.testing.assert_array_equal(np.asarray(jax.grad(loss_fused)(s)),
                                  np.asarray(jax.grad(loss_comp)(s)))
    # the clip gate: coordinates outside (0, 1) get zero gradient
    g = np.asarray(jax.grad(loss_fused)(s))
    outside = (np.asarray(s) < 0.0) | (np.asarray(s) > 1.0)
    np.testing.assert_array_equal(g[outside], 0.0)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("k", [1, 10])
def test_fused_vmap_grad_equals_composed(impl, k):
    spec = _mk()
    rng = np.random.RandomState(5)
    S = jnp.asarray(rng.randn(k, spec.n) * 0.7 + 0.3, jnp.float32)
    V = jnp.asarray(rng.randn(k, *spec.shape), jnp.float32)
    steps = jnp.arange(k, dtype=jnp.uint32) + 3

    def g_fused():
        def loss(s_, st, v_):
            return jnp.vdot(
                ops.sample_reconstruct(spec, clip_probs(s_), st, impl=impl),
                v_)

        return jax.vmap(jax.grad(loss))(S, steps, V)

    def g_comp():
        # auto_batch default: vmap lowers the composed custom_vjp onto
        # the SAME batched backward as the fused op — exactness needs
        # like-for-like lowering, not per-client replication
        def loss(s_, st, v_):
            p = clip_probs(s_)
            z = sample_mask_hash(p, spec.seed, spec.tensor_id, st)
            z_st = p + jax.lax.stop_gradient(z - p)
            return jnp.vdot(ops.reconstruct(spec, z_st, impl=impl), v_)

        return jax.vmap(jax.grad(loss))(S, steps, V)

    np.testing.assert_array_equal(np.asarray(g_fused()),
                                  np.asarray(g_comp()))


def test_fused_vmap_lowers_onto_batched(monkeypatch):
    """vmap(sample_reconstruct) must hit the natively-batched fused
    forward, and vmap(grad(...)) the batched backward rule."""
    spec = _mk(seed=21)
    P_ = _probs(spec, 4, seed=21)
    steps = jnp.arange(4, dtype=jnp.uint32)
    fwd_calls, bwd_calls = [], []
    real_f, real_b = ops._fwd_many_fused, ops._bwd_many
    monkeypatch.setattr(ops, "_fwd_many_fused",
                        lambda *a, **k: (fwd_calls.append(1),
                                         real_f(*a, **k))[1])
    monkeypatch.setattr(ops, "_bwd_many",
                        lambda *a, **k: (bwd_calls.append(1),
                                         real_b(*a, **k))[1])
    jax.vmap(lambda p_, s_: ops.sample_reconstruct(spec, p_, s_))(P_, steps)
    assert fwd_calls, "batched fused fwd rule never fired under vmap"
    V = jnp.asarray(np.random.RandomState(1).randn(4, *spec.shape),
                    jnp.float32)
    jax.vmap(jax.grad(
        lambda p_, s_, v_: jnp.vdot(ops.sample_reconstruct(spec, p_, s_),
                                    v_)
    ))(P_, steps, V)
    assert bwd_calls, "batched bwd rule never fired under vmap(grad)"


# ---------------------------------------------------------------------------
# fused sample_pack == composed sample -> pack
# ---------------------------------------------------------------------------

class TestSamplePack:
    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_single_matches_composed(self, impl):
        spec = _mk()
        p = _probs(spec)
        step = jnp.uint32(5)
        want = np.asarray(pack_mask(
            sample_mask_hash(p, spec.seed, spec.tensor_id, step)))
        got = np.asarray(ops.sample_pack(spec, p, step, impl=impl))
        assert got.dtype == np.uint32
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    @pytest.mark.parametrize("k", KS)
    def test_batched_matches_composed(self, impl, k):
        spec = _mk()
        P_ = _probs(spec, k)
        steps = jnp.arange(k, dtype=jnp.uint32) + 1
        want = np.asarray(pack_mask(
            sample_mask_hash(P_, spec.seed, spec.tensor_id, steps)))
        got = np.asarray(ops.sample_pack_batched(spec, P_, steps, impl=impl))
        np.testing.assert_array_equal(got, want)
        got_v = np.asarray(jax.vmap(
            lambda p_, s_: ops.sample_pack(spec, p_, s_, impl=impl)
        )(P_, steps))
        np.testing.assert_array_equal(got_v, want)

    def test_small_window_falls_back(self):
        # window 16 < 32: the pallas impl must fall back to the jnp
        # oracle (partial lanes cannot be emitted blockwise)
        spec = _mk((40,), 2.0, 3, 16, seed=2)
        assert spec.window % 32 != 0
        p = _probs(spec)
        step = jnp.uint32(3)
        want = np.asarray(pack_mask(
            sample_mask_hash(p, spec.seed, spec.tensor_id, step)))
        got = np.asarray(ops.sample_pack(spec, p, step, impl="pallas"))
        assert got.shape == (packed_len(spec.n),)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the architectural claim: no (K, n) f32 mask in the fused pallas jaxpr
# ---------------------------------------------------------------------------

def _eqn_out_shapes(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "dtype", None) is not None:
                acc.append((tuple(aval.shape), str(aval.dtype)))
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", None)
            if inner is not None:
                _eqn_out_shapes(inner, acc)
            elif hasattr(param, "eqns"):
                _eqn_out_shapes(param, acc)
    return acc


def test_no_mask_slab_in_fused_pallas_jaxpr():
    """The fused Pallas path must not materialize the (K, n) f32 mask
    anywhere in its jaxpr — the draw lives in-block at (window, K).
    The composed path DOES materialize it (detector sanity check)."""
    spec = _mk()
    k = 10
    P_ = _probs(spec, k)
    steps = jnp.arange(k, dtype=jnp.uint32)
    slab = ((k, spec.n), "float32")

    fused = jax.make_jaxpr(
        lambda P: ops.sample_reconstruct_batched(spec, P, steps,
                                                 impl="pallas")
    )(P_)
    fused_shapes = _eqn_out_shapes(fused.jaxpr, [])
    assert slab not in fused_shapes, (
        "fused pallas path materializes the (K, n) f32 mask slab"
    )

    composed = jax.make_jaxpr(
        lambda P: ops.reconstruct_batched(
            spec, sample_mask_hash(P, spec.seed, spec.tensor_id, steps),
            impl="pallas")
    )(P_)
    assert slab in _eqn_out_shapes(composed.jaxpr, []), (
        "detector failed: composed path should materialize the mask"
    )

    # same claim for the fused upload: lanes come out, no f32 mask
    pack = jax.make_jaxpr(
        lambda P: ops.sample_pack_batched(spec, P, steps, impl="pallas")
    )(P_)
    assert slab not in _eqn_out_shapes(pack.jaxpr, [])


# ---------------------------------------------------------------------------
# federated: fused == composed across transports, vmap and shard_map
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
    xs, ys = next(client_batch_stream(clients, 32, E, seed=0))
    batch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
    return zspecs, state, batch, K, E


def _round_scores(fed_setup, aggregate, mask_path):
    from repro.models.mlp import mlp_loss

    zspecs, state, batch, K, E = fed_setup
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                          aggregate=aggregate, mask_path=mask_path)
    st, met = jax.jit(
        lambda s, b, k: federated_round(zspecs, s, mlp_loss, b, k, cfg)
    )(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(met["loss"]))
    return jax.tree.map(np.asarray, st["scores"])


def test_round_fused_equals_composed_all_transports(fed_setup):
    base = _round_scores(fed_setup, "mean_f32", "composed")
    for agg in STRATEGIES:
        for mask_path in ("fused", "composed"):
            got = _round_scores(fed_setup, agg, mask_path)
            for p in base:
                np.testing.assert_array_equal(
                    base[p], got[p],
                    err_msg=f"{agg}/{mask_path} differs at {p}",
                )


def test_local_update_emits_native_lanes(fed_setup):
    """Packed transports receive uint32 wire lanes from local_update —
    no post-hoc pack of an f32 mask slab."""
    from repro.models.mlp import mlp_loss

    zspecs, state, batch, K, E = fed_setup
    b0 = jax.tree.map(lambda x: x[0], batch)
    for mask_path in ("fused", "composed"):
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              aggregate="psum_u32", mask_path=mask_path)
        z_new, _, _ = jax.jit(
            lambda s, b, k, cfg=cfg: local_update(zspecs, s, mlp_loss, b,
                                                  k, cfg)
        )(state, b0, jax.random.PRNGKey(0))
        for p, spec in zspecs.specs.items():
            assert z_new[p].dtype == jnp.uint32, (mask_path, p)
            assert z_new[p].shape == (packed_len(spec.n),), (mask_path, p)
    # the f32 strategy still gets f32 masks
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                          aggregate="mean_f32")
    z_new, _, _ = jax.jit(
        lambda s, b, k: local_update(zspecs, s, mlp_loss, b, k, cfg)
    )(state, b0, jax.random.PRNGKey(0))
    for p, spec in zspecs.specs.items():
        assert z_new[p].dtype == jnp.float32
        assert z_new[p].shape == (spec.n,)


def test_discretize_keeps_packed_wire(fed_setup):
    """Discretized uploads are binary, so packed transports keep their
    wire (no silent mean_f32 downgrade): lanes on the wire, scores
    bit-identical to the f32 strategy, packed bytes in the metrics."""
    from repro.models.mlp import mlp_loss

    zspecs, state, batch, K, E = fed_setup
    b0 = jax.tree.map(lambda x: x[0], batch)
    cfg_p = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                            mode="discretize", aggregate="psum_u32")
    z_new, _, _ = jax.jit(
        lambda s, b, k: local_update(zspecs, s, mlp_loss, b, k, cfg_p)
    )(state, b0, jax.random.PRNGKey(0))
    for p, spec in zspecs.specs.items():
        assert z_new[p].dtype == jnp.uint32
        assert z_new[p].shape == (packed_len(spec.n),)
    outs, mets = {}, {}
    for agg in ("mean_f32", "psum_u32"):
        cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                              mode="discretize", aggregate=agg)
        st, met = jax.jit(
            lambda s, b, k, cfg=cfg: federated_round(zspecs, s, mlp_loss,
                                                     b, k, cfg)
        )(state, batch, jax.random.PRNGKey(0))
        outs[agg] = jax.tree.map(np.asarray, st["scores"])
        mets[agg] = met
    for p in outs["mean_f32"]:
        np.testing.assert_array_equal(outs["mean_f32"][p],
                                      outs["psum_u32"][p])
    assert float(mets["psum_u32"]["uplink_bytes_per_client"]) < float(
        mets["mean_f32"]["uplink_bytes_per_client"])


def test_sharded_fused_equals_vmap_and_composed(fed_setup):
    """shard_map path == vmap path == composed, bit for bit, per
    transport (the draw words coincide across execution paths)."""
    from repro.models.mlp import mlp_loss

    mesh = data_mesh_or_skip(4)
    zspecs, state, batch, K, E = fed_setup
    state_specs = jax.tree.map(lambda _: P(), state)
    met_specs = round_metric_specs()
    base = _round_scores(fed_setup, "mean_f32", "composed")
    for agg in STRATEGIES:
        for mask_path in ("fused", "composed"):
            cfg = FederatedConfig(num_clients=K, local_steps=E,
                                  local_lr=0.1, aggregate=agg,
                                  mask_path=mask_path)

            def body(st, b, k, cfg=cfg):
                b = jax.tree.map(lambda x: x[0], b)
                return sharded_client_update(zspecs, st, mlp_loss, b, k,
                                             cfg)

            with jax.set_mesh(mesh):
                f = shard_map(body, ("data",),
                              (state_specs, P("data"), P()),
                              (state_specs, met_specs))
                ns, _ = jax.jit(f)(state, batch, jax.random.PRNGKey(0))
            for p in base:
                np.testing.assert_array_equal(
                    base[p], np.asarray(ns["scores"][p]),
                    err_msg=f"shard_map {agg}/{mask_path} differs at {p}",
                )


def test_fused_model_sharded_dispatch():
    """The 'model'-mesh branch: a shard_count>1 spec with model_size
    routes the fused op through the sharded reconstruction — exact vs
    the composed sharded path (same draw, same local chunks)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 4 on CPU)")
    spec = make_qspec(0, (8, 6, 16), 16, compression=2.0, d=4,
                      window=32, seed=3, major_axis=2, shard_count=4)
    p = _probs(spec, seed=13)
    step = jnp.uint32(2)
    mesh = jax.make_mesh((4,), ("model",))
    with jax.set_mesh(mesh):
        got = np.asarray(
            ops.sample_reconstruct(spec, p, step, model_size=4))
        z = sample_mask_hash(p, spec.seed, spec.tensor_id, step)
        want = np.asarray(ops.reconstruct(spec, z, model_size=4,
                                          auto_batch=False))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# MaskProgram / sample_weights
# ---------------------------------------------------------------------------

class TestMaskProgram:
    def _zsetup(self):
        template = {
            "l0": {"kernel": jnp.zeros((64, 128)), "bias": jnp.zeros((128,))},
            "l1": {"kernel": jnp.zeros((128, 32))},
        }
        zspecs = build_specs(template, ZamplingConfig(
            compression=4, d=4, window=128, min_size=256))
        state = init_state(jax.random.PRNGKey(0), zspecs)
        return zspecs, state

    def test_invalid_mode_raises(self):
        zspecs, _ = self._zsetup()
        with pytest.raises(ValueError, match="valid modes"):
            MaskProgram(zspecs, mode="bogus")
        with pytest.raises(ValueError, match="valid modes"):
            FederatedConfig(mode="bogus")
        with pytest.raises(ValueError, match="valid paths"):
            FederatedConfig(mask_path="bogus")

    def test_sample_weights_fused_equals_composed(self):
        zspecs, state = self._zsetup()
        key = jax.random.PRNGKey(2)
        w_f = sample_weights(zspecs, state, key, fused=True)
        w_c = sample_weights(zspecs, state, key, fused=False)
        for a, b in zip(jax.tree.leaves(w_f), jax.tree.leaves(w_c)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_modes_route_through_program(self):
        zspecs, state = self._zsetup()
        key = jax.random.PRNGKey(3)
        w_cont = sample_weights(zspecs, state, key, mode="continuous")
        w_disc = sample_weights(zspecs, state, key, mode="discretize")
        for a, b in zip(jax.tree.leaves(w_cont), jax.tree.leaves(w_disc)):
            assert a.shape == b.shape

    def test_upload_fused_equals_composed(self):
        zspecs, state = self._zsetup()
        step = jnp.uint32(17)
        for packed in (False, True):
            up_f = MaskProgram(zspecs, fused=True, packed=packed).upload(
                state["scores"], step)
            up_c = MaskProgram(zspecs, fused=False, packed=packed).upload(
                state["scores"], step)
            for p in up_f:
                np.testing.assert_array_equal(np.asarray(up_f[p]),
                                              np.asarray(up_c[p]))
                if packed:
                    assert up_f[p].dtype == jnp.uint32


# ---------------------------------------------------------------------------
# the hash mask stream itself
# ---------------------------------------------------------------------------

class TestMaskStream:
    def test_deterministic_and_binary(self):
        p = jnp.full((4096,), 0.3, jnp.float32)
        a = np.asarray(sample_mask_hash(p, 3, 1, jnp.uint32(5)))
        b = np.asarray(sample_mask_hash(p, 3, 1, jnp.uint32(5)))
        np.testing.assert_array_equal(a, b)
        assert set(np.unique(a)) <= {0.0, 1.0}
        assert abs(a.mean() - 0.3) < 0.05

    def test_steps_and_tensors_decorrelate(self):
        p = jnp.full((20000,), 0.5, jnp.float32)
        a = np.asarray(sample_mask_hash(p, 3, 1, jnp.uint32(5)))
        for args in ((3, 1, jnp.uint32(6)), (3, 2, jnp.uint32(5)),
                     (4, 1, jnp.uint32(5))):
            b = np.asarray(sample_mask_hash(p, *args))
            agree = (a == b).mean()
            assert 0.45 < agree < 0.55, (args, agree)

    def test_stream_disjoint_from_q_generation(self):
        # the 5-word mask stream must not alias the 4-word Q streams
        from repro.core.qspec import row_indices

        spec = _mk()
        u_mask = np.asarray(mask_u32(
            spec.seed, spec.tensor_id, jnp.uint32(0),
            jnp.arange(256, dtype=jnp.uint32)))
        idx = np.asarray(row_indices(spec, jnp.arange(256))).ravel()
        # crude: the mask words are full-range u32, not window indices
        assert u_mask.max() > spec.window * 1000

    def test_fold_word_counters_distinct(self):
        w = jnp.uint32(123)
        words = {int(fold_word(w, e)) for e in range(64)}
        assert len(words) == 64


# ---------------------------------------------------------------------------
# satellite: impl default validation + env override
# ---------------------------------------------------------------------------

class TestImplDefault:
    def test_set_default_impl_rejects_unknown(self):
        with pytest.raises(ValueError, match="valid impls"):
            ops.set_default_impl("bogus")
        assert ops._default_impl() == "ref"  # unchanged after the raise

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_RECONSTRUCT_IMPL", "pallas")
        assert ops._default_impl() == "pallas"
        monkeypatch.setenv("REPRO_RECONSTRUCT_IMPL", "bogus")
        with pytest.raises(ValueError, match="valid impls"):
            ops._default_impl()
        monkeypatch.delenv("REPRO_RECONSTRUCT_IMPL")
        assert ops._default_impl() == "ref"

    def test_env_override_routes_dispatch(self, monkeypatch):
        spec = _mk(seed=31)
        p = _probs(spec, seed=31)
        step = jnp.uint32(1)
        want = np.asarray(ops.sample_reconstruct(spec, p, step,
                                                 impl="pallas"))
        monkeypatch.setenv("REPRO_RECONSTRUCT_IMPL", "pallas")
        got = np.asarray(ops.sample_reconstruct(spec, p, step))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# satellite: analytic vs exact wire accounting
# ---------------------------------------------------------------------------

class TestWireAccountingCrossCheck:
    def _zspecs(self, window):
        # window=16 + the (40, 40) leaf: n = 25 windows x 16 = 400,
        # NOT a multiple of 32 -> real uint32 lane padding on the wire
        template = {
            "l0": {"kernel": jnp.zeros((40, 40)), "bias": jnp.zeros((128,))},
            "l1": {"kernel": jnp.zeros((128, 32))},
        }
        return build_specs(template, ZamplingConfig(
            compression=4, d=4, window=window, min_size=256))

    @pytest.mark.parametrize("window", [16, 128])
    def test_wire_keys_match_metering_exactly(self, window):
        zspecs = self._zspecs(window)
        bits = zspecs.comm_bits_per_round(packed=True)
        rep = round_wire_report(zspecs, "psum_u32", 10)
        assert bits["client_up_wire"] == 8 * rep["uplink_bytes_per_client"]
        assert bits["server_down_wire"] == 8 * rep[
            "downlink_bytes_per_client"]
        rep_f32 = round_wire_report(zspecs, "mean_f32", 10)
        bits_u = zspecs.comm_bits_per_round(packed=False)
        assert bits_u["client_up_wire"] == 8 * rep_f32[
            "uplink_bytes_per_client"]

    @pytest.mark.parametrize("window", [16, 128])
    def test_analytic_delta_is_padding_plus_dense(self, window):
        """The idealized ``client_up = n`` undercounts by exactly the
        uint32 lane padding + the dense f32 leaves — pinned here."""
        zspecs = self._zspecs(window)
        bits = zspecs.comm_bits_per_round(packed=True)
        pad = sum(32 * packed_len(s.n) - s.n for s in zspecs.specs.values())
        dense = 32 * zspecs.dense_total
        assert bits["client_up_wire"] - bits["client_up"] == pad + dense
        if window == 16:
            assert pad > 0  # small windows really do pad lanes
        else:
            assert pad == 0  # window % 32 == 0: lanes tile exactly
