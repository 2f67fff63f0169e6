"""Wire-format transport layer (repro.comm).

 - bitpack edge cases (n % 32 != 0, n < 32, all-ones/all-zeros) and
   batched (K, n) pack/unpack;
 - the three transports bit-IDENTICAL (exact equality, not allclose)
   on the stacked vmap path, on a full ``federated_round``, and on the
   collective ``shard_map`` path over the forced 4-device CPU mesh;
 - the psum(pack) ≡ pack-side popcount sum ≡ f32 psum property on the
   mesh;
 - exact wire accounting: packed uplink ≤ 1/32 of f32 + lane padding;
 - ``FederatedConfig.aggregate`` validation at construction;
 - the ``REPRO_BATCH_MAP_THRESHOLD`` env override (satellite).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from jax.sharding import PartitionSpec as P

from _helpers import data_mesh_or_skip, round_metric_specs

from repro.comm.bitpack import (
    pack_mask,
    packed_len,
    packed_popcount_sum,
    unpack_mask,
)
from repro.comm.metering import mask_uplink_bytes, round_wire_report, wire_table
from repro.comm.protocol import get_transport, resolve_transport, transport_names
from repro.comm.shardmap import shard_map
from repro.core import FederatedConfig, ZamplingConfig, build_specs, init_state
from repro.core.federated import WIRE_METRIC_KEYS, federated_round, sharded_client_update
from repro.data import client_batch_stream, iid_client_split, make_teacher_dataset
from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss

STRATEGIES = ("mean_f32", "psum_u32", "allgather_packed")


def _binary(shape, seed=0, p=0.5):
    return (np.random.RandomState(seed).rand(*shape) < p).astype(np.float32)


# ---------------------------------------------------------------------------
# bitpack
# ---------------------------------------------------------------------------

class TestBitpackEdges:
    @pytest.mark.parametrize("n", [1, 5, 31, 32, 33, 63, 700])
    def test_roundtrip_odd_sizes(self, n):
        z = _binary((n,), seed=n)
        packed = pack_mask(jnp.asarray(z))
        assert packed.shape == (packed_len(n),)
        assert packed.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(unpack_mask(packed, n)), z)

    @pytest.mark.parametrize("n", [5, 32, 70])
    @pytest.mark.parametrize("fill", [0.0, 1.0])
    def test_all_ones_all_zeros(self, n, fill):
        z = np.full((n,), fill, np.float32)
        packed = pack_mask(jnp.asarray(z))
        np.testing.assert_array_equal(np.asarray(unpack_mask(packed, n)), z)
        counts = packed_popcount_sum(packed[None], n)
        np.testing.assert_array_equal(np.asarray(counts),
                                      z.astype(np.uint32))

    @pytest.mark.parametrize("k", [1, 3])
    @pytest.mark.parametrize("n", [5, 33, 256])
    def test_batched_pack_unpack(self, k, n):
        Z = _binary((k, n), seed=n + k)
        packed = pack_mask(jnp.asarray(Z))
        assert packed.shape == (k, packed_len(n))
        np.testing.assert_array_equal(np.asarray(unpack_mask(packed, n)), Z)
        np.testing.assert_array_equal(
            np.asarray(packed_popcount_sum(packed, n)),
            Z.sum(0).astype(np.uint32),
        )

    def test_pack_composes_with_vmap(self):
        Z = _binary((4, 70), seed=2)
        a = np.asarray(jax.vmap(pack_mask)(jnp.asarray(Z)))
        b = np.asarray(pack_mask(jnp.asarray(Z)))
        np.testing.assert_array_equal(a, b)

    @settings(max_examples=12, deadline=None)
    @given(n=st.integers(1, 700), seed=st.integers(0, 10_000))
    def test_popcount_equals_f32_sum(self, n, seed):
        Z = _binary((5, n), seed=seed)
        counts = packed_popcount_sum(pack_mask(jnp.asarray(Z)), n)
        np.testing.assert_array_equal(
            np.asarray(counts).astype(np.float32), Z.sum(0)
        )


# ---------------------------------------------------------------------------
# transports: stacked path
# ---------------------------------------------------------------------------

class TestTransportsStacked:
    @pytest.mark.parametrize("n", [31, 32, 777])
    def test_bit_identical_across_strategies(self, n):
        Z = jnp.asarray(_binary((10, n), seed=n, p=0.3))
        outs = {s: np.asarray(get_transport(s).aggregate_stacked(Z))
                for s in STRATEGIES}
        for s in STRATEGIES[1:]:
            np.testing.assert_array_equal(outs["mean_f32"], outs[s])
        np.testing.assert_array_equal(outs["mean_f32"],
                                      np.asarray(Z).sum(0) / 10)

    def test_mean_alias(self):
        assert get_transport("mean") is get_transport("mean_f32")
        assert resolve_transport("psum_u32", "continuous").name == "mean_f32"

    def test_unknown_transport_raises(self):
        with pytest.raises(ValueError, match="registered"):
            get_transport("nope")


# ---------------------------------------------------------------------------
# transports: full federated_round (exact equality of new_scores)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fed_setup():
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


def _round_outputs(fed_setup, aggregate):
    zspecs, state, batch, K, E = fed_setup
    cfg = FederatedConfig(num_clients=K, local_steps=E, local_lr=0.1,
                          aggregate=aggregate)
    return jax.jit(
        lambda s, b, k: federated_round(zspecs, s, mlp_loss, b, k, cfg)
    )(state, batch, jax.random.PRNGKey(0))


def test_round_scores_bit_identical(fed_setup):
    base, base_met = _round_outputs(fed_setup, "mean_f32")
    for s in ("psum_u32", "allgather_packed", "mean"):
        got, _ = _round_outputs(fed_setup, s)
        for p in base["scores"]:
            np.testing.assert_array_equal(
                np.asarray(base["scores"][p]), np.asarray(got["scores"][p]),
                err_msg=f"{s} differs from mean_f32 at {p}",
            )
    assert np.isfinite(float(base_met["loss"]))


def test_round_metrics_report_wire_bytes(fed_setup):
    zspecs, state, batch, K, E = fed_setup
    _, met_f32 = _round_outputs(fed_setup, "mean_f32")
    _, met_packed = _round_outputs(fed_setup, "psum_u32")
    for k in WIRE_METRIC_KEYS:
        assert k in met_f32 and k in met_packed
    # the packed mask traffic is 1/32 of f32 + at most one lane/tensor
    mask_f32 = sum(4 * s.n for s in zspecs.specs.values())
    mask_packed = sum(4 * packed_len(s.n) for s in zspecs.specs.values())
    dense = float(met_f32["uplink_bytes_per_client"]) - mask_f32
    assert float(met_packed["uplink_bytes_per_client"]) == mask_packed + dense
    assert mask_packed <= mask_f32 / 32 + 4 * len(zspecs.specs)
    assert float(met_packed["uplink_bytes_round"]) == K * (
        mask_packed + dense
    )


def test_wire_accounting_ratio():
    """uplink(packed) ≤ 1/32 of f32 + lane padding, exactly metered."""
    for n in (31, 32, 1000, 12345):
        f32_b = mask_uplink_bytes(get_transport("mean_f32"), n)
        for s in ("psum_u32", "allgather_packed"):
            b = mask_uplink_bytes(get_transport(s), n)
            assert b == 4 * packed_len(n)
            assert b <= f32_b / 32 + 4


def test_wire_table_rows(fed_setup):
    zspecs, _, _, K, _ = fed_setup
    rows = wire_table(zspecs, K)
    assert {r["strategy"] for r in rows} == set(STRATEGIES)
    by = {r["strategy"]: r for r in rows}
    assert by["mean_f32"]["uplink_vs_f32"] == 1.0
    assert by["psum_u32"]["uplink_bytes_per_client"] < by["mean_f32"][
        "uplink_bytes_per_client"
    ]
    rep = round_wire_report(zspecs, "mean", K)
    assert rep["transport"] == "mean_f32"  # alias resolves in metering


# ---------------------------------------------------------------------------
# FederatedConfig validation (satellite)
# ---------------------------------------------------------------------------

class TestConfigValidation:
    def test_unknown_strategy_raises_at_construction(self):
        with pytest.raises(ValueError) as ei:
            FederatedConfig(aggregate="allgather_paked")  # typo
        for name in STRATEGIES:
            assert name in str(ei.value)

    @pytest.mark.parametrize("name", STRATEGIES + ("mean",))
    def test_registered_strategies_accepted(self, name):
        assert FederatedConfig(aggregate=name).aggregate == name


# ---------------------------------------------------------------------------
# collective path: forced 4-device CPU mesh
# ---------------------------------------------------------------------------

def _data_mesh(size=4):
    return data_mesh_or_skip(size)


class TestCollectivePath:
    def test_psum_pack_popcount_f32_all_agree(self):
        """psum of unpacked u32 bits ≡ pack-side popcount of the
        gathered lanes ≡ f32 psum — the property behind psum_u32 and
        allgather_packed being interchangeable."""
        mesh = _data_mesh()
        n = 100  # not a multiple of 32
        Z = jnp.asarray(_binary((4, n), seed=3, p=0.4))

        def body(zl):
            z = zl[0]
            packed = pack_mask(z)
            s_f32 = jax.lax.psum(z.astype(jnp.float32), ("data",))
            s_u32 = jax.lax.psum(
                unpack_mask(packed, n, dtype=jnp.uint32), ("data",)
            )
            lanes = jax.lax.all_gather(packed, ("data",), axis=0)
            s_pop = packed_popcount_sum(lanes, n)
            return s_f32[None], s_u32[None], s_pop[None]

        with jax.set_mesh(mesh):
            f = shard_map(body, ("data",), P("data", None),
                          (P(None, None),) * 3)
            s_f32, s_u32, s_pop = jax.jit(f)(Z)
        want = np.asarray(Z).sum(0)
        np.testing.assert_array_equal(np.asarray(s_f32)[0], want)
        np.testing.assert_array_equal(
            np.asarray(s_u32)[0].astype(np.float32), want
        )
        np.testing.assert_array_equal(
            np.asarray(s_pop)[0].astype(np.float32), want
        )

    def test_collective_aggregate_bit_identical(self):
        mesh = _data_mesh()
        n = 777
        Z = jnp.asarray(_binary((4, n), seed=4, p=0.6))
        outs = {}
        for s in STRATEGIES:
            t = get_transport(s)

            def body(zl, t=t):
                return t.aggregate_collective(zl[0], ("data",))[None]

            with jax.set_mesh(mesh):
                f = shard_map(body, ("data",), P("data", None),
                              P(None, None))
                outs[s] = np.asarray(jax.jit(f)(Z))[0]
        for s in STRATEGIES[1:]:
            np.testing.assert_array_equal(outs["mean_f32"], outs[s])
        np.testing.assert_array_equal(outs["mean_f32"],
                                      np.asarray(Z).sum(0) / 4)

    def test_sharded_client_update_bit_identical(self, fed_setup):
        """The full production body under shard_map: every transport
        yields the same aggregated scores, bit for bit."""
        mesh = _data_mesh()
        zspecs, state, batch, K, E = fed_setup
        state_specs = jax.tree.map(lambda _: P(), state)
        met_specs = round_metric_specs()
        outs = {}
        for s in STRATEGIES:
            cfg = FederatedConfig(num_clients=K, local_steps=E,
                                  local_lr=0.1, aggregate=s)

            def body(st, b, k, cfg=cfg):
                b = jax.tree.map(lambda x: x[0], b)
                return sharded_client_update(zspecs, st, mlp_loss, b, k,
                                             cfg)

            with jax.set_mesh(mesh):
                f = shard_map(body, ("data",),
                              (state_specs, P("data"), P()),
                              (state_specs, met_specs))
                ns, met = jax.jit(f)(state, batch, jax.random.PRNGKey(0))
            outs[s] = jax.tree.map(np.asarray, ns["scores"])
            assert np.isfinite(float(met["loss"]))
        for s in STRATEGIES[1:]:
            for p in outs["mean_f32"]:
                np.testing.assert_array_equal(outs["mean_f32"][p],
                                              outs[s][p])

    def test_sharded_metrics_use_mesh_size(self, fed_setup):
        """Wire metrics on the sharded path count the mesh axis size,
        not cfg.num_clients (which is unused there and may differ)."""
        mesh = _data_mesh()
        zspecs, state, batch, K, E = fed_setup
        cfg = FederatedConfig(num_clients=10, local_steps=E,
                              local_lr=0.1, aggregate="psum_u32")
        state_specs = jax.tree.map(lambda _: P(), state)
        met_specs = round_metric_specs()

        def body(st, b, k):
            b = jax.tree.map(lambda x: x[0], b)
            return sharded_client_update(zspecs, st, mlp_loss, b, k, cfg)

        with jax.set_mesh(mesh):
            f = shard_map(body, ("data",),
                          (state_specs, P("data"), P()),
                          (state_specs, met_specs))
            _, met = jax.jit(f)(state, batch, jax.random.PRNGKey(0))
        assert float(met["uplink_bytes_round"]) == 4 * float(
            met["uplink_bytes_per_client"]
        )


# ---------------------------------------------------------------------------
# REPRO_BATCH_MAP_THRESHOLD env override (satellite)
# ---------------------------------------------------------------------------

class TestBatchMapThresholdEnv:
    def test_env_overrides_default(self, monkeypatch):
        from repro.core.reconstruct import (
            _BATCH_MAP_THRESHOLD,
            _batch_map_threshold,
        )

        assert _batch_map_threshold() == _BATCH_MAP_THRESHOLD
        monkeypatch.setenv("REPRO_BATCH_MAP_THRESHOLD", "123")
        assert _batch_map_threshold() == 123

    def test_both_strategies_agree(self, monkeypatch):
        """Forcing the crossover either way must not change results."""
        from repro.core.qspec import make_qspec
        from repro.core.reconstruct import reconstruct_batched_ref

        spec = make_qspec(1, (64, 96), 64 * 1, compression=8.0, d=8,
                          window=256, seed=11)
        Z = jnp.asarray(_binary((3, spec.n), seed=5))
        monkeypatch.setenv("REPRO_BATCH_MAP_THRESHOLD", "1")  # force map
        w_map = np.asarray(reconstruct_batched_ref(spec, Z))
        monkeypatch.setenv("REPRO_BATCH_MAP_THRESHOLD", str(1 << 62))
        w_fused = np.asarray(reconstruct_batched_ref(spec, Z))
        np.testing.assert_allclose(w_map, w_fused, rtol=1e-5, atol=1e-6)


def test_transport_names_stable():
    names = transport_names(include_aliases=False)
    assert names == sorted(STRATEGIES)
    assert "mean" in transport_names()
