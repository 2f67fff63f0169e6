"""Transpose-plan backward: ``grad_z = Q^T grad_w`` as a gather.

Contract (core/transpose_plan.py): EXACT equality per ordering mode
(the same plan always sums each coordinate's incoming edges in the
same order), ``allclose`` across ordering modes and against the
scatter oracle.  Sweeps d / window / shard_count / non-divisible
``rows_per_window % bm``, zero-in-degree columns, chunked and sharded
paths, and ``vmap(grad(local_update))`` through the federated round
on the forced 4-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.qspec import make_qspec, padded_row_valid
from repro.core.reconstruct import (
    grad_z_batched_ref,
    grad_z_plan_batched_ref,
    grad_z_plan_ref,
    grad_z_ref,
    grad_z_scatter_batched_ref,
    grad_z_scatter_ref,
    materialize_q,
)
from repro.core.transpose_plan import (
    _bin_block_plan,
    _host_eval,
    build_block_plan,
    build_transpose_plan,
    resolve_bwd_path,
    SUB_ROWS,
    row_plan,
    set_default_bwd_path,
    sub_block_rows,
)
from repro.kernels import ops
from repro.kernels.qz_reconstruct import (
    qz_reconstruct_batched_bwd_plan,
    qz_reconstruct_bwd_plan,
)

# (shape, compression, d, window, make_qspec kwargs) — sweeps d and
# window, shard-major layouts, and a d=1 diagonal-ish spec
SWEEP = [
    ((64, 96), 8.0, 8, 256, {}),
    ((512,), 2.0, 4, 64, {}),
    ((1000,), 4.0, 1, 128, {}),
    ((8, 6, 16), 2.0, 4, 32, dict(major_axis=2, shard_count=4)),
    ((64, 48), 2.0, 4, 32, dict(major_axis=1, shard_count=16)),
]


def _mk(shape, c, d, window, kw=None, seed=11):
    fan = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
    return make_qspec(1, shape, fan, compression=c, d=d, window=window,
                      seed=seed, **(kw or {}))


def _g(spec, seed=1, k=None):
    r = np.random.RandomState(seed)
    shape = spec.shape if k is None else (k, *spec.shape)
    return jnp.asarray(r.randn(*shape), jnp.float32)


@pytest.mark.parametrize("shape,c,d,window,kw", SWEEP)
def test_plan_allclose_scatter_and_dense(shape, c, d, window, kw):
    spec = _mk(shape, c, d, window, kw)
    g = _g(spec)
    plan = np.asarray(grad_z_plan_ref(spec, g))
    scatter = np.asarray(grad_z_scatter_ref(spec, g))
    np.testing.assert_allclose(plan, scatter, rtol=1e-4, atol=1e-5)
    q = np.asarray(materialize_q(spec))
    dense = np.einsum("mn,m->n", q, np.asarray(g).reshape(-1))
    np.testing.assert_allclose(plan, dense, rtol=1e-4, atol=1e-4)
    # batched: one plan constant, K clients
    G = _g(spec, seed=2, k=3)
    np.testing.assert_allclose(
        np.asarray(grad_z_plan_batched_ref(spec, G)),
        np.asarray(grad_z_scatter_batched_ref(spec, G)),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("order", ["canonical", "slot"])
def test_plan_exact_per_ordering_mode(order):
    """Same ordering mode -> bit-identical results, jit or not."""
    spec = _mk((64, 96), 8.0, 8, 256, {})
    g = _g(spec)
    a = np.asarray(grad_z_plan_ref(spec, g, order=order))
    b = np.asarray(jax.jit(
        lambda g_: grad_z_plan_ref(spec, g_, order=order))(g))
    c = np.asarray(jax.jit(  # a distinct jit cache entry
        lambda g_, o=order: grad_z_plan_ref(spec, g_, o))(g))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_plan_orders_allclose_cross_mode():
    spec = _mk((64, 96), 8.0, 8, 256, {})
    g = _g(spec)
    a = np.asarray(grad_z_plan_ref(spec, g, order="canonical"))
    b = np.asarray(grad_z_plan_ref(spec, g, order="slot"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # the two plans really do order edges differently where deg > 1
    pa = build_transpose_plan(spec, "canonical")
    pb = build_transpose_plan(spec, "slot")
    np.testing.assert_array_equal(pa.counts, pb.counts)
    assert (pa.rows != pb.rows).any()


def test_zero_in_degree_columns():
    """Coordinates no row ever touches must get exactly zero grad."""
    spec = _mk((1000,), 4.0, 1, 128, {})
    plan = build_transpose_plan(spec)
    dead = np.flatnonzero(plan.counts == 0)
    assert dead.size > 0, "sweep spec no longer has zero-degree columns"
    g = _g(spec)
    out = np.asarray(grad_z_plan_ref(spec, g))
    np.testing.assert_array_equal(out[dead], 0.0)
    np.testing.assert_allclose(out, np.asarray(grad_z_scatter_ref(spec, g)),
                               rtol=1e-4, atol=1e-5)


def test_plan_counts_match_valid_edges():
    for shape, c, d, window, kw in SWEEP:
        spec = _mk(shape, c, d, window, kw)
        plan = build_transpose_plan(spec)
        assert plan.n_edges == spec.m * spec.d  # padding rows excluded
        assert plan.deg == int(plan.counts.max())
        assert (np.asarray(plan.vals)[..., :] != 0).sum() <= plan.n_edges


@pytest.mark.parametrize("bm", [64, 256])
def test_pallas_plan_bwd_matches(bm):
    """Block plan kernel, incl. rows_per_window % bm != 0 re-binning."""
    spec = _mk((900, 30), 16.0, 8, 128, {})
    assert spec.rows_per_window % bm != 0
    g = _g(spec).reshape(-1)
    want = np.asarray(grad_z_scatter_ref(spec, g.reshape(spec.shape)))
    got = np.asarray(qz_reconstruct_bwd_plan(spec, g, bm=bm,
                                             interpret=True))
    got2 = np.asarray(qz_reconstruct_bwd_plan(spec, g, bm=bm,
                                              interpret=True))
    np.testing.assert_array_equal(got, got2)  # its own ordering mode
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    G = _g(spec, seed=3, k=3).reshape(3, -1)
    wantb = np.asarray(
        grad_z_scatter_batched_ref(spec, G.reshape(3, *spec.shape)))
    gotb = np.asarray(qz_reconstruct_batched_bwd_plan(spec, G, bm=bm,
                                                      interpret=True))
    np.testing.assert_allclose(gotb, wantb, rtol=1e-4, atol=1e-4)


def test_block_plan_geometry():
    spec = _mk((900, 30), 16.0, 8, 128, {})
    bp = build_block_plan(spec, 64)
    assert bp.bpw == -(-spec.rows_per_window // 64)
    assert (bp.sub, bp.nsub) == (64, 1)  # under a lane tile: one sub-block
    assert bp.rows.shape == (spec.num_windows, bp.bpw, 1, bp.deg,
                             spec.window)
    assert bp.rows.max() < 64  # block-relative
    flat = build_transpose_plan(spec)
    # re-binning preserves the edge multiset per coordinate
    assert (bp.vals != 0).sum() == flat.n_edges


# the benchmark cell's layer0/kernel (784x300, compression 32, d=10,
# window 128, tensor 1, seed 0), as build_specs makes it
def _cell_layer0():
    return make_qspec(1, (784, 300), 784, compression=32, d=10,
                      window=128, seed=0)


def _cell_edges(plan):
    """Each (window, block, coordinate) cell's live edges in summation
    order: rows block-relative, as (cells, L) rows / vals, live first."""
    nw, bpw, nsub, deg, win = plan.rows.shape
    rows = plan.rows + (np.arange(nsub) * plan.sub)[:, None, None]
    rows = np.moveaxis(rows, 4, 2).reshape(nw * bpw * win, nsub * deg)
    vals = np.moveaxis(plan.vals, 4, 2).reshape(rows.shape)
    live = vals != 0
    perm = np.argsort(~live, axis=1, kind="stable")
    rows = np.where(np.take_along_axis(live, perm, 1),
                    np.take_along_axis(rows, perm, 1), 0)
    return rows, np.take_along_axis(vals, perm, 1), live.sum(1)


@pytest.mark.parametrize("order", ["canonical", "slot"])
@pytest.mark.parametrize("which", ["rpw_not_256", "rpw_under_256",
                                   "cell_layer0"])
def test_block_plan_sub_blocks_keep_edge_order(which, order):
    """SUB_ROWS-row sub-blocks, concatenated in sub-block order, give each
    (window, 256-row block, coordinate) the 256-row plan's edges: the
    same sequence in canonical order (ascending row), the sequence
    grouped by sub-block in slot order."""
    spec = {"rpw_not_256": lambda: _mk((900, 30), 16.0, 8, 128, {}),
            "rpw_under_256": lambda: _mk((64, 96), 6.0, 4, 32, {}),
            "cell_layer0": _cell_layer0}[which]()
    assert spec.rows_per_window % 256 != 0
    assert (spec.rows_per_window < 256) == (which == "rpw_under_256")
    assert sub_block_rows(spec, 256) == SUB_ROWS
    sub, whole = build_block_plan(spec, 256, order), _bin_block_plan(
        spec, 256, 256, order)
    nsub = 256 // SUB_ROWS
    assert (sub.sub, sub.nsub, whole.nsub) == (SUB_ROWS, nsub, 1)
    assert sub.rows.shape == (spec.num_windows, whole.bpw, nsub, sub.deg,
                              spec.window)
    assert sub.rows.max() < SUB_ROWS and sub.deg < whole.deg
    rows_s, vals_s, n_s = _cell_edges(sub)
    rows_w, vals_w, n_w = _cell_edges(whole)
    np.testing.assert_array_equal(n_s, n_w)
    width = int(n_w.max())
    rows_s, vals_s = rows_s[:, :width], vals_s[:, :width]
    rows_w, vals_w = rows_w[:, :width], vals_w[:, :width]
    # the 256-row sequence, grouped by sub-block (padding stays last)
    key = np.where(np.arange(width) < n_w[:, None], rows_w // SUB_ROWS,
                   nsub)
    grp = np.argsort(key, axis=1, kind="stable")
    grouped_r = np.take_along_axis(rows_w, grp, 1)
    grouped_v = np.take_along_axis(vals_w, grp, 1)
    if order == "canonical":  # already ascending rows: grouping is a no-op
        np.testing.assert_array_equal(grouped_r, rows_w)
        np.testing.assert_array_equal(grouped_v, vals_w)
    else:
        assert (grouped_r != rows_w).any()
    np.testing.assert_array_equal(rows_s, grouped_r)
    np.testing.assert_array_equal(vals_s, grouped_v)
    if which == "cell_layer0":
        assert (SUB_ROWS, whole.deg, sub.deg) == (64, 43, 18)
        assert whole.onehot_elems == 43 * 256 * 128
        assert sub.onehot_elems == 4 * 18 * 64 * 128


def test_sub_block_rows_rule():
    big = _mk((900, 30), 16.0, 8, 128, {})  # 1,929 rows per window
    assert SUB_ROWS == 64
    assert sub_block_rows(big, 256) == 64
    assert sub_block_rows(big, 512) == 64
    assert sub_block_rows(big, 128) == 64
    assert sub_block_rows(big, 64) == 64  # a block of one sub-block
    assert sub_block_rows(big, 32) == 32
    small = _mk((64, 96), 2.0, 4, 32, {})  # one sub-block per window
    assert small.rows_per_window <= 64
    assert sub_block_rows(small, 256) == 256
    assert build_block_plan(small, 256).nsub == 1


def _chain_oracle(spec, G, bm=256):
    """float32 numpy ``Qᵀ g``: each coordinate adds its incoming edges
    ``val·g`` one at a time, ascending (source row, slot) within each
    ``bm``-row block of its window, then adds the block sums in block
    order.  Built from the row plan alone."""
    G = np.asarray(G, np.float32)
    nc, nw, rpw, win = G.shape[0], spec.num_windows, spec.rows_per_window, \
        spec.window
    gidx, vals = row_plan(spec)
    rp = np.arange(spec.m_pad)
    with _host_eval():
        valid = np.asarray(padded_row_valid(spec, rp))
    gp = np.zeros((nc, spec.m_pad), np.float32)
    gp[:, :spec.m] = G
    bpw = -(-rpw // bm)
    w, j = np.meshgrid(np.arange(nw), np.arange(bpw), indexing="ij")
    acc = np.zeros((nc, nw, bpw, win), np.float32)
    for r in range(bm):  # one source row of every (window, block) at once
        local = j * bm + r
        row = np.where(local < rpw, w * rpw + local, 0)
        live = (local < rpw) & valid[row]
        for k in range(spec.d):
            term = np.where(live, vals[row, k] * gp[:, row], np.float32(0))
            c = gidx[row, k] % win
            acc[:, w, j, c] = acc[:, w, j, c] + term
    gz = np.zeros((nc, nw, win), np.float32)
    for jb in range(bpw):
        gz = gz + acc[:, :, jb]
    return gz.reshape(nc, -1), np.abs(acc).sum(2).reshape(nc, -1)


def _cell_layer0_cotangents(spec, k=10, seed=7):
    return jnp.asarray(np.random.RandomState(seed).randn(k, spec.m),
                       jnp.float32)


def test_pallas_plan_bwd_chain_cell_layer0():
    """The sub-binned kernel at the cell's layer-0 widths (K=10) against
    the float32 chain.  The CPU interpreter's compiler contracts some
    multiply-adds (the 256-row kernel too), so the gap is bounded at
    1e-6 of the summed term magnitudes that each chain rounds at."""
    spec = _cell_layer0()
    G = _cell_layer0_cotangents(spec)
    want, mag = _chain_oracle(spec, G)
    got = np.asarray(qz_reconstruct_batched_bwd_plan(spec, G,
                                                     interpret=True))
    assert got.shape == want.shape == (10, spec.n)
    assert np.all(np.abs(got - want) <= 1e-6 * (np.abs(want) + mag))


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="TPU only")
def test_pallas_plan_bwd_chain_exact_on_tpu():
    """On the chip the chain is a plain multiply then add: the
    sub-binned kernel gives the float32 chain's bits, and the 256-row
    one-hot kernel's."""
    from repro.kernels.qz_reconstruct import _bwd_plan_call

    spec = _cell_layer0()
    G = _cell_layer0_cotangents(spec)
    want, _ = _chain_oracle(spec, G)
    got = np.asarray(qz_reconstruct_batched_bwd_plan(spec, G))
    whole = np.asarray(_bwd_plan_call(
        spec, G, _bin_block_plan(spec, 256, 256, "canonical"),
        interpret=False))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, whole)


def test_chunked_plan_matches_unchunked():
    spec = _mk((777,), 2.0, 4, 64, {})
    z = jnp.asarray(np.random.RandomState(4).rand(spec.n), jnp.float32)
    v = _g(spec, seed=5)

    def grad_with(chunks):
        return jax.grad(lambda z_: jnp.vdot(
            ops.reconstruct(spec, z_, chunks=chunks, auto_batch=False),
            v))(z)

    a, b = np.asarray(grad_with(1)), np.asarray(grad_with(5))
    np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)
    G = _g(spec, seed=6, k=3)
    Z = jnp.asarray(np.random.RandomState(7).rand(3, spec.n), jnp.float32)

    def bgrad_with(chunks):
        return jax.grad(lambda Z_: jnp.vdot(
            ops.reconstruct_batched(spec, Z_, chunks=chunks), G))(Z)

    np.testing.assert_allclose(np.asarray(bgrad_with(5)),
                               np.asarray(bgrad_with(1)),
                               rtol=1e-4, atol=1e-5)


def test_env_gate_routes_paths(monkeypatch):
    """REPRO_BWD_PLAN picks the trace-time path: each gated trace must
    reproduce its oracle BIT-exactly."""
    spec = _mk((64, 96), 8.0, 8, 256, {}, seed=21)
    z = jnp.asarray(np.random.RandomState(8).rand(spec.n), jnp.float32)
    v = _g(spec, seed=9)

    def traced_grad():
        # a fresh closure per call: a fresh trace reads the gate
        return np.asarray(jax.grad(lambda z_: jnp.vdot(
            ops.reconstruct(spec, z_, auto_batch=False), v))(z))

    monkeypatch.setenv("REPRO_BWD_PLAN", "scatter")
    np.testing.assert_array_equal(
        traced_grad(), np.asarray(grad_z_scatter_ref(spec, v)))
    monkeypatch.setenv("REPRO_BWD_PLAN", "plan")
    np.testing.assert_array_equal(
        traced_grad(), np.asarray(grad_z_plan_ref(spec, v)))
    monkeypatch.setenv("REPRO_BWD_PLAN", "plan:slot")
    np.testing.assert_array_equal(
        traced_grad(), np.asarray(grad_z_plan_ref(spec, v, order="slot")))
    monkeypatch.setenv("REPRO_BWD_PLAN", "bogus")
    with pytest.raises(ValueError, match="REPRO_BWD_PLAN"):
        resolve_bwd_path()


def test_set_default_bwd_path_validates():
    with pytest.raises(ValueError, match="valid paths"):
        set_default_bwd_path("bogus")
    assert resolve_bwd_path("plan") == ("plan", "canonical")
    assert resolve_bwd_path("plan:slot") == ("plan", "slot")
    assert resolve_bwd_path("scatter") == ("scatter", None)


def test_grad_z_ref_dispatches_to_plan_by_default():
    spec = _mk((64, 96), 8.0, 8, 256, {}, seed=23)
    g = _g(spec, seed=10)
    np.testing.assert_array_equal(np.asarray(grad_z_ref(spec, g)),
                                  np.asarray(grad_z_plan_ref(spec, g)))
    G = _g(spec, seed=11, k=3)
    np.testing.assert_array_equal(
        np.asarray(grad_z_batched_ref(spec, G)),
        np.asarray(grad_z_plan_batched_ref(spec, G)))


def test_sharded_plan_matches_scatter_and_global(monkeypatch):
    from tests._helpers import data_mesh_or_skip
    from repro.kernels.qz_sharded import sharded_grad_z, sharded_grad_z_batched

    mesh = data_mesh_or_skip(4, "model")
    spec = make_qspec(0, (8, 6, 16), 16, compression=2.0, d=4, window=32,
                      seed=3, major_axis=2, shard_count=4)
    g, G = _g(spec, seed=12), _g(spec, seed=13, k=3)
    with jax.set_mesh(mesh):
        got = np.asarray(sharded_grad_z(spec, g, 4))
        gotb = np.asarray(sharded_grad_z_batched(spec, G, 4))
        monkeypatch.setenv("REPRO_BWD_PLAN", "scatter")
        sc = np.asarray(sharded_grad_z(spec, g, 4))
        scb = np.asarray(sharded_grad_z_batched(spec, G, 4))
        monkeypatch.delenv("REPRO_BWD_PLAN")
    # the shard-local plan is a window-slice of the global plan: the
    # per-coordinate edge order coincides, so single-client sharded is
    # bit-identical to the global plan path
    np.testing.assert_array_equal(got, np.asarray(grad_z_plan_ref(spec, g)))
    np.testing.assert_allclose(got, sc, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gotb, scb, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        gotb, np.asarray(grad_z_plan_batched_ref(spec, G)),
        rtol=1e-4, atol=1e-5)


def test_federated_round_plan_vs_scatter(monkeypatch):
    """vmap(grad(local_update)) through a full round on the 4-device
    mesh topology: the plan backward must be deterministic (exact
    across reruns) and allclose to a scatter-gated round."""
    from repro.core.federated import FederatedConfig, federated_round
    from repro.core.zampling import ZamplingConfig, build_specs, init_state
    from repro.data import client_batch_stream, iid_client_split, make_teacher_dataset
    from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss

    ds = make_teacher_dataset(n_train=300, n_test=50, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=2.0, d=5, window=128, min_size=256))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    K, E = 4, 2
    xs, ys = next(client_batch_stream(iid_client_split(ds, K), 16, E,
                                      seed=0))
    batch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1)

    def run():
        st, met = jax.jit(lambda s, b, k: federated_round(
            zspecs, s, mlp_loss, b, k, cfg))(state, batch,
                                             jax.random.PRNGKey(0))
        assert np.isfinite(float(met["loss"]))
        return jax.tree.map(np.asarray, st["scores"])

    plan_scores = run()
    plan_again = run()
    monkeypatch.setenv("REPRO_BWD_PLAN", "scatter")
    scatter_scores = run()
    for p in plan_scores:
        np.testing.assert_array_equal(plan_scores[p], plan_again[p])
        np.testing.assert_allclose(plan_scores[p], scatter_scores[p],
                                   rtol=1e-4, atol=1e-5)
