"""Streaming cohort accumulator (``FederatedConfig.stream_chunk``).

The central contract: the chunk-scan round is BIT-IDENTICAL on scores
to the one-shot slab round — for every transport, every chunk size
(dividing K or not), weight-1 and faulted, on the vmap and the
4-device shard_map driver.  The uplink vote counts are uint32 (packed
transports) or f32 sums of binary·small-integer products (mean_f32),
both exact under re-association, so chunked folding changes nothing.
Dense f32 leaves and the loss are sums of real numbers — those agree
up to reduction order only (same tolerance as the cross-driver
contract in tests/test_faults.py).

Also pinned here: the architectural claim that the streaming jaxpr
never materializes the (K, lanes) upload slab, the transport fold
hooks against the integer oracle, the streamed-fit host-staging driver
against ``federated_fit``, and the analytic peak-memory model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm import get_transport, streaming_peak_bytes, upload_slab_bytes
from repro.comm.bitpack import pack_mask, packed_len
from repro.core import FederatedConfig, ZamplingConfig, build_specs, init_state
from repro.core.federated import (
    PARTICIPATION_METRIC_KEYS,
    ROUND_METRIC_KEYS,
    federated_round,
)
from repro.data import (
    cohort_batch_stream,
    iid_client_split,
    make_teacher_dataset,
)
from repro.fault import ClientPopulation, FaultPlan
from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss
from repro.train import federated_fit, streamed_federated_fit

K, E, B = 6, 2, 16
TRANSPORTS = ["mean_f32", "psum_u32", "allgather_packed"]
CHUNKS = [2, 3, 4, 5]  # 4 and 5 do not divide K=6 -> padded last chunk
PLAN = FaultPlan(dropout=0.3, straggler=0.1, corrupt=0.2, duplicate=0.1,
                 seed=5)
WEIGHTS = np.array([5, 2, 9, 1, 4, 7], np.uint32)


@pytest.fixture(scope="module")
def setup():
    ds = make_teacher_dataset(n_train=600, n_test=50, seed=0)
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


def _round(zspecs, state, batch, key, cfg, **kw):
    return jax.jit(lambda s, b, k: federated_round(
        zspecs, s, mlp_loss, b, k, cfg, **kw))(state, batch, key)


def _assert_scores_exact_dense_close(a, b):
    for p in a["scores"]:
        np.testing.assert_array_equal(
            np.asarray(a["scores"][p]), np.asarray(b["scores"][p]))
    for p in a["dense"]:
        np.testing.assert_allclose(
            np.asarray(a["dense"][p]).astype(np.float32),
            np.asarray(b["dense"][p]).astype(np.float32),
            rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Config validation + slab fall-through
# ---------------------------------------------------------------------------

def test_stream_chunk_must_be_nonnegative():
    with pytest.raises(ValueError):
        FederatedConfig(num_clients=K, stream_chunk=-1)


def test_chunk_at_least_k_falls_through_to_slab(setup):
    _, zspecs, state, batch = setup
    key = jax.random.PRNGKey(7)
    st0, m0 = _round(zspecs, state, batch, key, _cfg("psum_u32"))
    st1, m1 = _round(zspecs, state, batch, key,
                     _cfg("psum_u32", stream_chunk=K))
    for p in st0["scores"]:
        np.testing.assert_array_equal(np.asarray(st0["scores"][p]),
                                      np.asarray(st1["scores"][p]))
    assert np.asarray(m0["loss"]).view(np.uint32) == \
        np.asarray(m1["loss"]).view(np.uint32)


# ---------------------------------------------------------------------------
# Streaming == slab: every transport, every chunking, plain and faulted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", TRANSPORTS)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_streaming_matches_slab_weight_one(setup, name, chunk):
    _, zspecs, state, batch = setup
    key = jax.random.PRNGKey(7)
    slab, m0 = _round(zspecs, state, batch, key, _cfg(name))
    stream, m1 = _round(zspecs, state, batch, key,
                        _cfg(name, stream_chunk=chunk))
    _assert_scores_exact_dense_close(slab, stream)
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-6)
    assert set(m1) == set(ROUND_METRIC_KEYS)
    assert float(m1["num_participating"]) == K
    assert float(m1["weight_sum"]) == K
    assert float(m1["round_skipped"]) == 0.0
    assert float(m1["uplink_bytes_round"]) == float(m0["uplink_bytes_round"])


@pytest.mark.parametrize("name", TRANSPORTS)
@pytest.mark.parametrize("chunk", CHUNKS)
def test_streaming_matches_slab_faulted(setup, name, chunk):
    """Padded chunk lanes replay real clients' fault draws at live=0:
    they must influence nothing — votes, weight sum, counters, loss,
    realized bytes all equal the slab round's."""
    _, zspecs, state, batch = setup
    key = jax.random.PRNGKey(7)
    kw = dict(client_ids=jnp.arange(K, dtype=jnp.uint32),
              weights=jnp.asarray(WEIGHTS), faults=PLAN)
    slab, m0 = _round(zspecs, state, batch, key, _cfg(name), **kw)
    stream, m1 = _round(zspecs, state, batch, key,
                        _cfg(name, stream_chunk=chunk), **kw)
    _assert_scores_exact_dense_close(slab, stream)
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=1e-6)
    for mk in PARTICIPATION_METRIC_KEYS + ("weight_sum", "round_skipped"):
        assert float(m0[mk]) == float(m1[mk]), mk
    assert float(m0["uplink_bytes_round"]) == float(m1["uplink_bytes_round"])
    assert 0 < float(m1["num_participating"]) < K, \
        "plan injected no faults at this seed; pick another seed"


def test_streaming_skips_below_min_clients(setup):
    _, zspecs, state, batch = setup
    plan = FaultPlan(dropout=0.99, seed=2)
    cfg = _cfg("psum_u32", min_clients=K, stream_chunk=2)
    st, m = _round(zspecs, state, batch, jax.random.PRNGKey(7), cfg,
                   client_ids=jnp.arange(K, dtype=jnp.uint32),
                   weights=jnp.asarray(WEIGHTS), faults=plan)
    assert float(m["round_skipped"]) == 1.0
    for p in st["scores"]:
        np.testing.assert_array_equal(np.asarray(st["scores"][p]),
                                      np.asarray(state["scores"][p]))


# ---------------------------------------------------------------------------
# Cross-driver: streaming vmap == 4-device shard_map slab
# ---------------------------------------------------------------------------

def test_streaming_vmap_matches_shard_map_slab(setup):
    from repro.comm import shard_map
    from repro.core.federated import sharded_client_update
    from jax.sharding import PartitionSpec as P

    _, zspecs, state, batch = setup
    mesh = data_mesh_or_skip()
    k4 = 4
    b4 = jax.tree.map(lambda x: x[:k4], batch)
    w4 = jnp.asarray(WEIGHTS[:k4])
    cfg = _cfg("psum_u32", stream_chunk=2)
    key = jax.random.PRNGKey(7)
    stv, mv = _round(zspecs, state, b4, key, cfg,
                     client_ids=jnp.arange(k4, dtype=jnp.uint32),
                     weights=w4, faults=PLAN)
    state_specs = jax.tree.map(lambda _: P(), state)

    def body(s, b, kk, i, ww):
        b = jax.tree.map(lambda x: x[0], b)
        return sharded_client_update(zspecs, s, mlp_loss, b, kk,
                                     cfg, faults=PLAN, client_id=i[0],
                                     weight=ww[0])

    with jax.set_mesh(mesh):
        f = shard_map(
            body, ("data",),
            (state_specs, P("data"), P(), P("data"), P("data")),
            (state_specs, round_metric_specs()))
        sts, ms = jax.jit(f)(state, b4, key,
                             jnp.arange(k4, dtype=jnp.uint32), w4)
    _assert_scores_exact_dense_close(stv, sts)
    for mk in PARTICIPATION_METRIC_KEYS:
        assert float(mv[mk]) == float(ms[mk]), mk


# ---------------------------------------------------------------------------
# Transport fold hooks == whole-stack aggregation (integer oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", TRANSPORTS)
def test_fold_hooks_match_stacked_aggregation(name):
    rng = np.random.RandomState(0)
    n, k, chunk = 203, 6, 2
    Z = rng.randint(0, 2, (k, n)).astype(np.float32)
    w = np.array([3, 1, 0, 7, 2, 5], np.uint32)
    t = get_transport(name)
    acc = t.stream_init(n)
    if t.packed_wire:
        lanes = pack_mask(jnp.asarray(Z))
        for c in range(0, k, chunk):
            acc = t.fold_stacked_packed_weighted(
                acc, lanes[c:c + chunk], n, jnp.asarray(w[c:c + chunk]))
        want = t.aggregate_stacked_packed_weighted(lanes, n, jnp.asarray(w))
    else:
        for c in range(0, k, chunk):
            acc = t.fold_stacked_weighted(
                acc, jnp.asarray(Z[c:c + chunk]), jnp.asarray(w[c:c + chunk]))
        want = t.aggregate_stacked_weighted(jnp.asarray(Z), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want))
    oracle = np.sum(Z.astype(np.int64) * w[:, None].astype(np.int64), axis=0)
    np.testing.assert_array_equal(np.asarray(acc).astype(np.int64), oracle)


# ---------------------------------------------------------------------------
# The architectural claim: no (K, lanes) upload slab in the streaming jaxpr
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


@pytest.mark.parametrize("name", ["mean_f32", "psum_u32"])
def test_no_upload_slab_in_streaming_jaxpr(setup, name):
    """With stream_chunk < K no equation anywhere in the round jaxpr may
    output a full-cohort upload (K, n) f32 mask or (K, lanes) uint32
    slab — only (chunk, ·) uploads exist.  The slab round DOES emit
    them (detector sanity)."""
    _, zspecs, state, batch = setup
    key = jax.random.PRNGKey(7)
    t = get_transport(name)
    if t.packed_wire:
        slabs = {((K, packed_len(s.n)), "uint32")
                 for s in zspecs.specs.values()}
    else:
        slabs = {((K, s.n), "float32") for s in zspecs.specs.values()}

    def jaxpr_shapes(cfg):
        closed = jax.make_jaxpr(lambda s, b, k: federated_round(
            zspecs, s, mlp_loss, b, k, cfg))(state, batch, key)
        return set(_eqn_out_shapes(closed.jaxpr, []))

    stream_shapes = jaxpr_shapes(_cfg(name, stream_chunk=2))
    assert not (slabs & stream_shapes), (
        f"streaming round materializes upload slab(s): "
        f"{slabs & stream_shapes}")
    slab_shapes = jaxpr_shapes(_cfg(name))
    assert slabs & slab_shapes, (
        "detector failed: slab round should materialize the upload slab")


# ---------------------------------------------------------------------------
# Fit drivers: scan-of-rounds and the host-staging streamed fit
# ---------------------------------------------------------------------------

def test_fit_with_stream_chunk_matches_slab_fit(setup):
    _, zspecs, state, batch = setup
    R = 2
    batches = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (R,) + x.shape), batch)
    ids = jnp.broadcast_to(jnp.arange(K, dtype=jnp.uint32), (R, K))
    w = jnp.broadcast_to(jnp.asarray(WEIGHTS), (R, K))
    key = jax.random.PRNGKey(9)
    st0, m0 = jax.jit(lambda s, b, k: federated_fit(
        zspecs, s, mlp_loss, b, k, _cfg("psum_u32"),
        client_ids=ids, weights=w, faults=PLAN))(state, batches, key)
    st1, m1 = jax.jit(lambda s, b, k: federated_fit(
        zspecs, s, mlp_loss, b, k, _cfg("psum_u32", stream_chunk=4),
        client_ids=ids, weights=w, faults=PLAN))(state, batches, key)
    _assert_scores_exact_dense_close(st0, st1)
    np.testing.assert_array_equal(
        np.asarray(m0["num_participating"]),
        np.asarray(m1["num_participating"]))


def test_streamed_fit_matches_federated_fit(setup):
    """The double-buffered host-staging driver replays the identical
    cohorts/batches, so its state must match the all-device slab fit
    bitwise on scores."""
    ds, zspecs, state, _ = setup
    clients = iid_client_split(ds, 10)
    pop = ClientPopulation(
        10, sample_counts=tuple(len(c.x_train) for c in clients), seed=4)
    R, csize = 3, 4
    cfg = FederatedConfig(num_clients=csize, local_steps=E, local_lr=0.1,
                          aggregate="psum_u32", stream_chunk=3)
    plan = FaultPlan(dropout=0.2, seed=11)
    key = jax.random.PRNGKey(2)
    stream = cohort_batch_stream(clients, pop, csize, B, E, seed=0)
    st0, m0 = streamed_federated_fit(zspecs, state, mlp_loss, stream, key,
                                     cfg, R, faults=plan)
    gen = cohort_batch_stream(clients, pop, csize, B, E, seed=0)
    rows = [next(gen) for _ in range(R)]
    batches = {"x": jnp.asarray(np.stack([r[2] for r in rows])),
               "y": jnp.asarray(np.stack([r[3] for r in rows]))}
    st1, m1 = jax.jit(lambda s, b, k: federated_fit(
        zspecs, s, mlp_loss, b, k, cfg,
        client_ids=jnp.asarray(np.stack([r[0] for r in rows])),
        weights=jnp.asarray(np.stack([r[1] for r in rows])),
        faults=plan))(state, batches, key)
    _assert_scores_exact_dense_close(st0, st1)
    np.testing.assert_array_equal(np.asarray(m0["num_participating"]),
                                  np.asarray(m1["num_participating"]))
    assert m0["loss"].shape == (R,)


# ---------------------------------------------------------------------------
# Peak-memory model: streaming bound is flat in K
# ---------------------------------------------------------------------------

def test_streaming_peak_bytes_flat_in_k(setup):
    _, zspecs, _, _ = setup
    chunk = 8
    peak = streaming_peak_bytes(zspecs, "psum_u32", chunk)
    # the peak is a function of the chunk only — flat as K sweeps
    assert streaming_peak_bytes(zspecs, "psum_u32", chunk) == peak
    # the slab grows linearly in K ...
    slab8 = upload_slab_bytes(zspecs, "psum_u32", chunk)
    assert upload_slab_bytes(zspecs, "psum_u32", 256) == 32 * slab8
    # ... so at K=256 it holds 32x the lanes the streaming round ever
    # keeps resident, and still dwarfs the peak with the (n,) vote
    # accumulator charged against streaming
    assert upload_slab_bytes(zspecs, "psum_u32", 256) / slab8 >= 25.0
    assert upload_slab_bytes(zspecs, "psum_u32", 256) > 6.0 * peak
