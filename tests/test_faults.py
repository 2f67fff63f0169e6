"""Fault-tolerant partial-participation round engine (fault package +
the weighted aggregation paths of core.federated).

Contracts under test:

- Cohort sampling is a deterministic K-of-N draw from the counter-hash
  stream — replayable on host and device, keyed on (seed, round) only.
- The weighted aggregation path with every client participating at
  weight 1 is BIT-IDENTICAL to the PR-5 unweighted path, on the vmap
  and the 4-device shard_map driver, for packed and f32 transports.
- Fault draws are deterministic in (plan.seed, round, client_id):
  the same seed produces the same faulted rounds on both drivers.
- A faulted round computes the exact weighted mean over survivors
  (transport-level integer oracle + survivor-subset replay).
- Rounds below ``min_clients`` degrade gracefully: state carried
  forward unchanged, ``round_skipped`` raised in the metrics.
- Server-side validation detects injected lane corruption.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm import get_transport, shard_map
from repro.comm.bitpack import pack_mask, packed_weighted_sum
from repro.core import FederatedConfig, ZamplingConfig, build_specs, init_state
from repro.core.federated import (
    PARTICIPATION_METRIC_KEYS,
    ROUND_METRIC_KEYS,
    federated_round,
    sharded_client_update,
)
from repro.data import (
    cohort_batch_stream,
    dirichlet_client_split,
    iid_client_split,
    make_teacher_dataset,
)
from repro.fault import (
    CORRUPT,
    DROP,
    OK,
    ClientPopulation,
    FaultPlan,
    corrupt_uploads,
    draw_faults,
    upload_counts,
    validate_uploads,
)
from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss
from repro.train import federated_fit, sharded_client_fit

K, E, B = 4, 2, 16


@pytest.fixture(scope="module")
def setup():
    ds = make_teacher_dataset(n_train=400, n_test=50, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=2.0, d=5, window=128, min_size=256))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    clients = iid_client_split(ds, K)
    xs, ys = [], []
    rng = np.random.RandomState(3)
    for c in clients:
        idx = rng.randint(0, len(c.x_train), (E, B))
        xs.append(c.x_train[idx])
        ys.append(c.y_train[idx])
    batch = {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}
    return ds, zspecs, state, batch


def _cfg(aggregate, **kw):
    return FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                           aggregate=aggregate, **kw)


def _assert_state_bits(a, b):
    for p in a["scores"]:
        np.testing.assert_array_equal(
            np.asarray(a["scores"][p]), np.asarray(b["scores"][p]))
    for p in a["dense"]:
        x, y = np.asarray(a["dense"][p]), np.asarray(b["dense"][p])
        if x.dtype == np.float32:
            np.testing.assert_array_equal(x.view(np.uint32),
                                          y.view(np.uint32))
        else:
            np.testing.assert_array_equal(x, y)


def _assert_cross_driver(a, b):
    """Cross-driver contract (the seed's, extended): scores are
    bit-identical; dense f32 leaves agree up to reduction order (XLA
    fuses the vmap stacked sum and the psum differently)."""
    for p in a["scores"]:
        np.testing.assert_array_equal(
            np.asarray(a["scores"][p]), np.asarray(b["scores"][p]))
    for p in a["dense"]:
        np.testing.assert_allclose(
            np.asarray(a["dense"][p]).astype(np.float32),
            np.asarray(b["dense"][p]).astype(np.float32),
            rtol=1e-6, atol=1e-7)


def _sharded_round(mesh, zspecs, state, batch, key, cfg, *, ids=None,
                   weights=None, faults=None):
    state_specs = jax.tree.map(lambda _: P(), state)
    in_specs = [state_specs, P("data"), P()]
    args = [state, batch, key]

    def body(s, b, k, *rest):
        b = jax.tree.map(lambda x: x[0], b)
        kw = {}
        if ids is not None:
            kw["client_id"] = rest[0][0]
        if weights is not None:
            kw["weight"] = rest[-1][0]
        return sharded_client_update(zspecs, s, mlp_loss, b, k, cfg,
                                     faults=faults, **kw)

    if ids is not None:
        in_specs.append(P("data"))
        args.append(jnp.asarray(ids, jnp.uint32))
    if weights is not None:
        in_specs.append(P("data"))
        args.append(jnp.asarray(weights, jnp.uint32))
    with jax.set_mesh(mesh):
        f = shard_map(body, ("data",), tuple(in_specs),
                      (jax.tree.map(lambda _: P(), state),
                       round_metric_specs()))
        return jax.jit(f)(*args)


# ---------------------------------------------------------------------------
# Cohort sampling + data staging
# ---------------------------------------------------------------------------

def test_cohort_sampler_properties():
    pop = ClientPopulation(23, seed=9)
    seen = set()
    for r in range(6):
        ids, w = pop.cohort_np(r, 7)
        assert ids.shape == (7,) and w.shape == (7,)
        assert len(np.unique(ids)) == 7
        assert (np.sort(ids) == ids).all()
        assert (ids < 23).all()
        assert (w == 1).all()  # no sample counts -> unit weights
        seen.add(tuple(ids.tolist()))
    assert len(seen) > 1, "cohort never varies across rounds"
    # replay: same (seed, round) -> same cohort, on host and on device
    ids0, _ = pop.cohort_np(2, 7)
    ids1, _ = pop.cohort_np(2, 7)
    np.testing.assert_array_equal(ids0, ids1)
    dev_ids, dev_w = jax.jit(lambda: pop.sample_cohort(2, 7))()
    np.testing.assert_array_equal(np.asarray(dev_ids), ids0)


def test_cohort_weights_are_sample_counts():
    counts = tuple(range(1, 11))
    pop = ClientPopulation(10, sample_counts=counts, seed=3)
    ids, w = pop.cohort_np(5, 4)
    np.testing.assert_array_equal(w, np.asarray(counts)[ids])


def test_dirichlet_split_partitions_and_weights():
    ds = make_teacher_dataset(n_train=500, n_test=20, seed=1)
    clients, hist = dirichlet_client_split(ds, 8, beta=0.3, seed=2)
    assert len(clients) == 8
    sizes = np.array([len(c.x_train) for c in clients])
    assert sizes.sum() == len(ds.x_train), "split is not a partition"
    assert (sizes >= 1).all(), "empty client escaped the rebalance"
    np.testing.assert_array_equal(hist.sum(axis=1), sizes)
    assert hist.sum() == len(ds.x_train)
    # non-IID: at least one client's label mix differs from uniform
    frac = hist / np.maximum(hist.sum(axis=1, keepdims=True), 1)
    assert np.abs(frac - frac.mean(axis=0)).max() > 0.05
    with pytest.raises(ValueError):
        dirichlet_client_split(ds, 4, beta=0.0)


def test_cohort_batch_stream_replays_sampler():
    ds = make_teacher_dataset(n_train=300, n_test=20, seed=0)
    clients, hist = dirichlet_client_split(ds, 10, beta=0.5, seed=0)
    pop = ClientPopulation(10, sample_counts=tuple(hist.sum(axis=1)), seed=4)
    stream = cohort_batch_stream(clients, pop, 3, B, E, seed=0)
    for r in range(3):
        ids, w, x, y = next(stream)
        want_ids, want_w = pop.cohort_np(r, 3)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(w, want_w)
        assert x.shape[:3] == (3, E, B)
        assert y.shape[:2] == (3, E)
    with pytest.raises(ValueError):
        next(cohort_batch_stream(clients[:5], pop, 3, B, E))


# ---------------------------------------------------------------------------
# Fault draws: determinism and rates
# ---------------------------------------------------------------------------

def test_fault_draw_determinism_and_codes():
    plan = FaultPlan(dropout=0.2, straggler=0.1, corrupt=0.1,
                     duplicate=0.1, seed=11)
    ids = jnp.arange(64, dtype=jnp.uint32)
    a = np.asarray(draw_faults(plan, 0, ids))
    b = np.asarray(jax.jit(lambda: draw_faults(plan, 0, ids))())
    np.testing.assert_array_equal(a, b)
    assert set(np.unique(a)).issubset({0, 1, 2, 3, 4})
    # a different round or seed reshuffles the outcome
    c = np.asarray(draw_faults(plan, 1, ids))
    d = np.asarray(draw_faults(
        FaultPlan(dropout=0.2, straggler=0.1, corrupt=0.1, duplicate=0.1,
                  seed=12), 0, ids))
    assert (a != c).any() and (a != d).any()
    # zero-rate plan never faults
    clean = np.asarray(draw_faults(FaultPlan(), 0, ids))
    assert (clean == OK).all()
    # empirical rate sanity on a large draw
    big = np.asarray(draw_faults(plan, 7, jnp.arange(20000, dtype=jnp.uint32)))
    assert abs(float(np.mean(big == DROP)) - 0.2) < 0.02


def test_fault_plan_validates_rates():
    with pytest.raises(ValueError):
        FaultPlan(dropout=0.7, straggler=0.4)
    with pytest.raises(ValueError):
        FaultPlan(dropout=-0.1)


# ---------------------------------------------------------------------------
# Weighted aggregation: integer oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mean_f32", "psum_u32", "allgather_packed"])
def test_weighted_sum_matches_integer_oracle(name):
    rng = np.random.RandomState(0)
    n = 203
    Z = rng.randint(0, 2, (K, n)).astype(np.float32)
    w = np.array([3, 1, 0, 7], np.uint32)
    want = np.sum(Z.astype(np.int64) * w[:, None].astype(np.int64), axis=0)
    t = get_transport(name)
    if t.packed_wire:
        # the native operand of the packed transports IS the lanes
        lanes = pack_mask(jnp.asarray(Z))
        counts = np.asarray(t.aggregate_stacked_packed_weighted(
            lanes, n, jnp.asarray(w)))
        np.testing.assert_array_equal(counts, want.astype(np.uint32))
    else:
        got = np.asarray(t.aggregate_stacked_weighted(
            jnp.asarray(Z), jnp.asarray(w)))
        np.testing.assert_array_equal(got, want.astype(np.float32))


def test_packed_weighted_sum_kernel():
    rng = np.random.RandomState(1)
    n = 97
    Z = rng.randint(0, 2, (5, n)).astype(np.float32)
    w = np.array([2, 5, 1, 0, 9], np.uint32)
    counts = np.asarray(packed_weighted_sum(
        pack_mask(jnp.asarray(Z)), n, jnp.asarray(w)))
    np.testing.assert_array_equal(
        counts, np.sum(Z.astype(np.int64) * w[:, None], axis=0))


# ---------------------------------------------------------------------------
# Round-level: weight-1 full participation == legacy path (bitwise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["mean_f32", "psum_u32", "allgather_packed"])
def test_weight_one_full_participation_matches_legacy(setup, name):
    _, zspecs, state, batch = setup
    cfg = _cfg(name)
    key = jax.random.PRNGKey(7)
    st0, m0 = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg))(state, batch, key)
    st1, m1 = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg,
        client_ids=jnp.arange(K, dtype=jnp.uint32),
        weights=jnp.ones(K, jnp.uint32),
        faults=FaultPlan()))(state, batch, key)
    _assert_state_bits(st0, st1)
    assert np.asarray(m0["loss"]).view(np.uint32) == \
        np.asarray(m1["loss"]).view(np.uint32)
    assert set(m1) == set(ROUND_METRIC_KEYS)
    assert float(m1["num_participating"]) == K
    assert float(m1["round_skipped"]) == 0.0
    assert float(m1["uplink_bytes_round"]) == float(m0["uplink_bytes_round"])


@pytest.mark.parametrize("name", ["mean_f32", "psum_u32", "allgather_packed"])
def test_weight_one_full_participation_matches_legacy_sharded(setup, name):
    _, zspecs, state, batch = setup
    mesh = data_mesh_or_skip()
    cfg = _cfg(name)
    key = jax.random.PRNGKey(7)
    st0, m0 = _sharded_round(mesh, zspecs, state, batch, key, cfg)
    st1, m1 = _sharded_round(
        mesh, zspecs, state, batch, key, cfg,
        ids=np.arange(K), weights=np.ones(K, np.uint32), faults=FaultPlan())
    _assert_state_bits(st0, st1)
    assert np.asarray(m0["loss"]).view(np.uint32) == \
        np.asarray(m1["loss"]).view(np.uint32)
    assert float(m1["weight_sum"]) == K


# ---------------------------------------------------------------------------
# Faulted rounds: vmap/shard_map parity, survivor replay, skip, bytes
# ---------------------------------------------------------------------------

PLAN = FaultPlan(dropout=0.3, straggler=0.1, corrupt=0.2, duplicate=0.1,
                 seed=5)


@pytest.mark.parametrize("name", ["psum_u32", "mean_f32"])
def test_faulted_round_vmap_sharded_bit_identical(setup, name):
    _, zspecs, state, batch = setup
    mesh = data_mesh_or_skip()
    cfg = _cfg(name)
    key = jax.random.PRNGKey(7)
    w = np.array([5, 2, 9, 1], np.uint32)
    stv, mv = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg,
        client_ids=jnp.arange(K, dtype=jnp.uint32),
        weights=jnp.asarray(w), faults=PLAN))(state, batch, key)
    sts, ms = _sharded_round(mesh, zspecs, state, batch, key, cfg,
                             ids=np.arange(K), weights=w, faults=PLAN)
    _assert_cross_driver(stv, sts)
    assert np.asarray(mv["loss"]).view(np.uint32) == \
        np.asarray(ms["loss"]).view(np.uint32)
    for mk in PARTICIPATION_METRIC_KEYS:
        assert float(mv[mk]) == float(ms[mk]), mk
    assert float(mv["num_participating"]) < K, \
        "plan injected no faults at this seed; pick another seed"


def test_faulted_round_equals_survivor_subset_round(setup):
    """Dropping clients is the SAME as never sampling them: a faulted
    full-cohort round reproduces the participation round run on just
    the survivors (draw words key on global client ids)."""
    _, zspecs, state, batch = setup
    plan = FaultPlan(dropout=0.5, seed=21)
    codes = np.asarray(draw_faults(plan, 0, jnp.arange(K, dtype=jnp.uint32)))
    surv = np.flatnonzero(codes == OK)
    assert 1 <= len(surv) < K, "seed 21 must drop some but not all of K=4"
    w = np.array([5, 2, 9, 1], np.uint32)
    cfg = _cfg("psum_u32")
    key = jax.random.PRNGKey(7)
    st_fault, m_fault = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg,
        client_ids=jnp.arange(K, dtype=jnp.uint32),
        weights=jnp.asarray(w), faults=plan))(state, batch, key)
    sub = jax.tree.map(lambda x: x[surv], batch)
    st_surv, m_surv = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg,
        client_ids=jnp.asarray(surv, jnp.uint32),
        weights=jnp.asarray(w[surv])))(state, sub, key)
    for p in st_fault["scores"]:
        np.testing.assert_array_equal(np.asarray(st_fault["scores"][p]),
                                      np.asarray(st_surv["scores"][p]))
    for p in st_fault["dense"]:
        np.testing.assert_allclose(np.asarray(st_fault["dense"][p]),
                                   np.asarray(st_surv["dense"][p]),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(m_fault["loss"]), float(m_surv["loss"]),
                               rtol=1e-6)
    assert float(m_fault["weight_sum"]) == float(w[surv].sum())


def test_skip_round_below_min_clients(setup):
    _, zspecs, state, batch = setup
    plan = FaultPlan(dropout=0.99, seed=2)
    cfg = _cfg("psum_u32", min_clients=3)
    codes = np.asarray(draw_faults(plan, 0, jnp.arange(K, dtype=jnp.uint32)))
    assert int(np.sum(codes == OK)) < 3
    st, m = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg, faults=plan))(state, batch,
                                                      jax.random.PRNGKey(7))
    assert float(m["round_skipped"]) == 1.0
    _assert_state_bits(state, st)


def test_duplicate_uploads_dedup_but_double_bytes(setup):
    _, zspecs, state, batch = setup
    plan = FaultPlan(duplicate=1.0, seed=0)
    cfg = _cfg("psum_u32")
    key = jax.random.PRNGKey(7)
    st0, m0 = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg))(state, batch, key)
    st1, m1 = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg, faults=plan))(state, batch, key)
    # dedup: the aggregate counts every client once -> bit-identical
    _assert_state_bits(st0, st1)
    assert float(m1["num_duplicates"]) == K
    assert float(m1["num_participating"]) == K
    # ... but the duplicated uploads were still paid for on the wire
    assert float(m1["uplink_bytes_round"]) == \
        2.0 * float(m0["uplink_bytes_round"])


def test_all_corrupt_round_is_excluded_and_skipped(setup):
    _, zspecs, state, batch = setup
    plan = FaultPlan(corrupt=1.0, seed=0)
    cfg = _cfg("psum_u32")
    st, m = jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg, faults=plan))(state, batch,
                                                      jax.random.PRNGKey(7))
    assert float(m["num_corrupt"]) == K
    assert float(m["num_participating"]) == 0.0
    assert float(m["round_skipped"]) == 1.0
    _assert_state_bits(state, st)
    # corrupt bytes still crossed the wire before validation rejected them
    assert float(m["uplink_bytes_round"]) > 0.0


# ---------------------------------------------------------------------------
# Upload validation primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [True, False])
def test_validation_detects_injected_corruption(setup, packed):
    _, zspecs, _, _ = setup
    rng = np.random.RandomState(0)
    plan = FaultPlan(corrupt=0.5, seed=13)
    z_all = {}
    for path, spec in zspecs.specs.items():
        z = rng.randint(0, 2, (K, spec.n)).astype(np.float32)
        z_all[path] = pack_mask(jnp.asarray(z)) if packed else jnp.asarray(z)
    declared = upload_counts(z_all, zspecs, packed=packed)
    clean_ok = np.asarray(validate_uploads(z_all, declared, zspecs,
                                           packed=packed))
    assert clean_ok.all(), "clean uploads must validate"
    mask = jnp.asarray(np.array([1, 0, 1, 0], bool))
    bad = corrupt_uploads(plan, z_all, declared, mask, 0,
                          jnp.arange(K, dtype=jnp.uint32), zspecs,
                          packed=packed)
    ok = np.asarray(validate_uploads(bad, declared, zspecs, packed=packed))
    np.testing.assert_array_equal(ok, ~np.asarray(mask))


# ---------------------------------------------------------------------------
# Scan drivers thread participation end-to-end
# ---------------------------------------------------------------------------

def test_fit_threads_participation(setup):
    """federated_fit with (R, K) id/weight slabs == R sequential
    participation rounds, faults and all."""
    _, zspecs, state, batch = setup
    R = 3
    pop = ClientPopulation(12, sample_counts=tuple(range(1, 13)), seed=6)
    ids = np.stack([pop.cohort_np(r, K)[0] for r in range(R)])
    w = np.stack([pop.cohort_np(r, K)[1] for r in range(R)])
    batches = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (R,) + x.shape), batch)
    cfg = _cfg("psum_u32")
    key = jax.random.PRNGKey(9)
    st_fit, mets = jax.jit(lambda s, b, k: federated_fit(
        zspecs, s, mlp_loss, b, k, cfg,
        client_ids=jnp.asarray(ids), weights=jnp.asarray(w),
        faults=PLAN))(state, batches, key)
    assert mets["round_skipped"].shape == (R,)
    st_seq = state
    for r, sub in enumerate(jax.random.split(key, R)):
        st_seq, m = jax.jit(lambda s, b, k, r=r: federated_round(
            zspecs, s, mlp_loss, b, k, cfg, round_index=jnp.uint32(r),
            client_ids=jnp.asarray(ids[r]), weights=jnp.asarray(w[r]),
            faults=PLAN))(st_seq, batch, sub)
        assert float(m["num_participating"]) == float(
            mets["num_participating"][r])
    _assert_state_bits(st_fit, st_seq)


def test_sharded_fit_threads_participation(setup):
    _, zspecs, state, batch = setup
    mesh = data_mesh_or_skip()
    R = 2
    ids = np.broadcast_to(np.arange(K, dtype=np.uint32), (R, K)).copy()
    w = np.broadcast_to(np.array([5, 2, 9, 1], np.uint32), (R, K)).copy()
    batches = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (R,) + x.shape), batch)
    cfg = _cfg("psum_u32")
    key = jax.random.PRNGKey(9)
    st_v, m_v = jax.jit(lambda s, b, k: federated_fit(
        zspecs, s, mlp_loss, b, k, cfg, client_ids=jnp.asarray(ids),
        weights=jnp.asarray(w), faults=PLAN))(state, batches, key)
    state_specs = jax.tree.map(lambda _: P(), state)
    met_specs = {mk: P() for mk in m_v}

    def body(s, b, k, i, ww):
        b = jax.tree.map(lambda x: x[:, 0], b)
        return sharded_client_fit(zspecs, s, mlp_loss, b, k, cfg,
                                  client_ids=i[:, 0], weights=ww[:, 0],
                                  faults=PLAN)

    with jax.set_mesh(mesh):
        f = shard_map(
            body, ("data",),
            (state_specs, P(None, "data"), P(), P(None, "data"),
             P(None, "data")),
            (state_specs, met_specs))
        st_s, m_s = jax.jit(f)(state, batches, key, jnp.asarray(ids),
                               jnp.asarray(w))
    _assert_cross_driver(st_v, st_s)
    np.testing.assert_array_equal(np.asarray(m_v["num_participating"]),
                                  np.asarray(m_s["num_participating"]))
