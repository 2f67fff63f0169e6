"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; full row dumps land in
experiments/results/<bench>.json.  ``--full`` switches to the paper's
full grids (hours on CPU); default is the quick CI-scale pass that
still exercises every claim.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table2,...]
"""

import argparse
import functools
import json
import os
import sys
import time


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


def _dump(name, rows):
    os.makedirs("experiments/results", exist_ok=True)
    with open(f"experiments/results/{name}.json", "w") as f:
        json.dump(rows, f, indent=2, default=str)


def bench_kernel_reconstruct():
    """Microbenchmark of the hot op, one row per impl.

    On CPU the 'pallas' impl runs in INTERPRET mode: its timing is a
    correctness-path artifact (the interpreter evaluates the one-hot
    contraction element by element), NOT kernel performance — so that
    row is keyed ``{"impl": "pallas_interpret"}`` with
    ``regression_comparable: False`` and must be EXCLUDED from any
    perf-regression comparison.  Hardware Pallas numbers (a TPU run)
    replace it under ``{"impl": "pallas"}`` when available.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.qspec import make_qspec
    from repro.kernels import ops

    spec = make_qspec(0, (1024, 1024), 1024, compression=32, d=8, window=512)
    z = jnp.asarray(
        (np.random.RandomState(0).rand(spec.n) < 0.5), jnp.float32
    )
    rows = []
    interp = ops._interpret()
    pallas_key = "pallas_interpret" if interp else "pallas"
    for impl, key in (("ref", "ref"), ("pallas", pallas_key)):
        f = jax.jit(lambda z_, impl=impl: ops.reconstruct(spec, z_, impl=impl))
        f(z).block_until_ready()
        t0 = time.perf_counter()
        iters = 20 if impl == "ref" else 3
        for _ in range(iters):
            f(z).block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6
        rows.append({
            "bench": "kernel_qz_reconstruct", "impl": key, "us": us,
            "m": spec.m, "n": spec.n, "d": spec.d,
            "regression_comparable": impl == "ref" or not interp,
        })
        _emit(f"kernel_qz_reconstruct_{key}", us,
              f"m={spec.m};n={spec.n};d={spec.d}")
    return rows


def bench_federated_round(full=False):
    """The batched multi-client reconstruction win (this PR's tentpole):
    vmap-of-single-client w = Qz vs the natively-batched kernel at
    K clients per host, forward and vmap(grad) chain, ref path on CPU.

    Rows land in experiments/results/fedround.json AND are merged into
    BENCH_reconstruct.json at the repo root (the cross-PR perf
    baseline; see scripts/ci.sh).

    NOTE (transpose-plan PR): the row plan is now a per-spec cached
    CONSTANT (core.transpose_plan), so the vmap-of-single-client
    baseline no longer pays K-times hash+Box–Muller regeneration —
    both sides start from the same baked plan and ``speedup`` measures
    only the contraction-strategy difference (the batched entry stays
    the memory-bounded choice: O(m_pad·d) temporaries vs the vmap
    mega-gather's O(K·m_pad·d)).  The headline backward comparison
    lives in the ``bwd_transpose_plan`` rows (bench_bwd).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.qspec import make_qspec
    from repro.kernels import ops

    spec = make_qspec(0, (1024, 1024), 1024, compression=32, d=8, window=512)
    rows = []
    for K in (4, 10, 32):
        Z = jnp.asarray(
            (np.random.RandomState(0).rand(K, spec.n) < 0.5), jnp.float32
        )
        V = jnp.asarray(
            np.random.RandomState(1).randn(K, *spec.shape), jnp.float32
        )
        f_vmap = jax.jit(jax.vmap(
            lambda z: ops.reconstruct(spec, z, auto_batch=False)
        ))
        f_bat = jax.jit(lambda Z_: ops.reconstruct_batched(spec, Z_))
        g_vmap = jax.jit(jax.vmap(jax.grad(
            lambda z, v: jnp.vdot(
                ops.reconstruct(spec, z, auto_batch=False), v
            )
        )))
        g_bat = jax.jit(jax.grad(
            lambda Z_, v: jnp.vdot(ops.reconstruct_batched(spec, Z_), v)
        ))
        g_bat = functools.partial(g_bat, v=V)
        np.testing.assert_allclose(
            np.asarray(f_vmap(Z)), np.asarray(f_bat(Z)), rtol=1e-4, atol=1e-4
        )
        jax.block_until_ready(g_bat(Z))  # compile before timing
        np.testing.assert_allclose(
            np.asarray(g_vmap(Z, V)), np.asarray(g_bat(Z)),
            rtol=1e-4, atol=1e-4,
        )
        iters = 5 if not full else 20
        out = {"bench": "federated_round_reconstruct", "K": K,
               "m": spec.m, "n": spec.n, "d": spec.d}
        for name, f in (("vmap", lambda: f_vmap(Z)),
                        ("batched", lambda: f_bat(Z)),
                        ("vmap_bwd", lambda: g_vmap(Z, V)),
                        ("batched_bwd", lambda: g_bat(Z))):
            f().block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                f().block_until_ready()
            out[f"{name}_us"] = (time.perf_counter() - t0) / iters * 1e6
        out["speedup"] = out["vmap_us"] / out["batched_us"]
        out["bwd_speedup"] = out["vmap_bwd_us"] / out["batched_bwd_us"]
        _emit(f"fedround_reconstruct_K{K}", out["batched_us"],
              f"vmap={out['vmap_us']:.0f}us"
              f";speedup={out['speedup']:.2f}x"
              f";bwd_speedup={out['bwd_speedup']:.2f}x")
        rows.append(out)
    return rows


def _merge_bench_root(rows):
    """Merge benchmark rows into BENCH_reconstruct.json at the repo
    root, keyed by (bench, K, strategy, impl, m_pad_d) — the perf
    trajectory across PRs (unused key fields are None per bench).
    Legacy pre-impl-keyed ``kernel_qz_reconstruct`` rows (one dict
    holding both a ref and an interpret-mode Pallas timing as if they
    were comparable) are dropped on sight."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_reconstruct.json")

    def _key(r):
        return (r.get("bench"), r.get("K"), r.get("strategy"),
                r.get("impl"), r.get("m_pad_d"))

    def _legacy(r):
        return (r.get("bench") == "kernel_qz_reconstruct"
                and "impl" not in r)

    try:
        with open(path) as f:
            kept = {_key(r): r for r in json.load(f) if not _legacy(r)}
    except FileNotFoundError:
        kept = {}
    except (OSError, ValueError, AttributeError, TypeError) as e:
        # unparseable/wrong-shape baseline: restart it, but say so —
        # the accumulated cross-PR history is being dropped
        print(f"WARNING: resetting corrupt {path}: {e}", file=sys.stderr)
        kept = {}
    for r in rows:
        if isinstance(r, dict) and "bench" in r:
            kept[_key(r)] = r
    with open(path, "w") as f:
        json.dump(list(kept.values()), f, indent=2, default=str)
    return path


def bench_wire(full=False):
    """Wire-format transports on a stacked client mask slab: time the
    three aggregation strategies, check bit-exactness, and report the
    exact wire bytes each puts on the network (comm.metering).

    Rows land in experiments/results/wire.json AND are merged into
    BENCH_reconstruct.json at the repo root keyed by
    (bench, K, strategy) — the CI staleness gate (scripts/ci.sh)
    asserts the committed JSON carries all three strategies.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.metering import mask_uplink_bytes
    from repro.comm.protocol import get_transport, transport_names

    # n is FIXED across quick/--full runs: the rows are keyed by
    # (bench, K, strategy) in BENCH_reconstruct.json, so a different n
    # would silently overwrite the cross-PR baseline with an
    # incomparable problem size (--full only raises iteration counts)
    n = 1 << 20
    rows = []
    for K in (10, 32):
        Z = jnp.asarray(
            (np.random.RandomState(0).rand(K, n) < 0.5), jnp.float32
        )
        names = transport_names(include_aliases=False)
        outs = {
            name: np.asarray(
                jax.jit(get_transport(name).aggregate_stacked)(Z)
            )
            for name in names
        }
        for name in names:
            np.testing.assert_array_equal(
                outs[name], outs["mean_f32"],
                err_msg=f"{name} not bit-exact vs mean_f32",
            )
        for name in names:
            t = get_transport(name)
            f = jax.jit(t.aggregate_stacked)
            f(Z).block_until_ready()
            iters = 20 if full else 5
            t0 = time.perf_counter()
            for _ in range(iters):
                f(Z).block_until_ready()
            us = (time.perf_counter() - t0) / iters * 1e6
            up = mask_uplink_bytes(t, n)
            f32_up = mask_uplink_bytes(get_transport("mean_f32"), n)
            rows.append({
                "bench": "wire_aggregate", "strategy": name, "K": K,
                "n": n, "us": us,
                "uplink_bytes_per_client": up,
                "uplink_vs_f32": up / f32_up,
            })
            _emit(f"wire_aggregate_{name}_K{K}", us,
                  f"up={up}B;vs_f32={up / f32_up:.4f}")
    return rows


def bench_fused(full=False):
    """Fused mask lifecycle vs the composed oracle (this PR's
    tentpole): ``w = Q·Bern(f(s))`` as one op vs sample -> reconstruct
    with the (K, n) f32 mask slab materialized between dispatches, and
    ``sample_pack`` (scores -> uint32 wire lanes) vs draw -> pack.

    Spec point: m = n = 2^20, compression 1, d = 1 — the paper's
    Zhou-et-al. retrieval configuration (Q diagonal), where the mask
    lifecycle IS the round and fusion matters most on CPU.  At the
    compression-32 / d-8 end the Q-gather dominates the ref path
    ~256:1, so the CPU-visible fused win shrinks to dispatch noise —
    there the win is architectural (the (K, n) f32 slab never crossing
    HBM; see kernels/qz_reconstruct.py).  n is FIXED across
    quick/--full runs: rows are keyed (bench, K) in
    BENCH_reconstruct.json and --full only raises iteration counts.

    Composed timings are the honest pre-fusion pipeline: separate
    dispatches with the straight-through ``p + sg(z - p)`` slab
    crossing memory between them — exactly what ``mask_path='composed'``
    (the bit-exact oracle) pays per round.  Fused and composed are
    timed INTERLEAVED (median of alternating runs) so load drift
    cancels; bit-exactness of fused vs composed is asserted before
    timing.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.bitpack import pack_mask
    from repro.core.qspec import make_qspec
    from repro.core.sampling import sample_mask_hash, sample_mask_st_hash
    from repro.kernels import ops

    spec = make_qspec(0, (1024, 1024), 1024, compression=1, d=1, window=512)
    iters = 30 if full else 12
    rows = []

    def ab(f_composed, f_fused):
        """Median us of each side, alternating composed/fused runs."""
        jax.block_until_ready(f_composed())  # compile + warm
        jax.block_until_ready(f_fused())
        ta, tb = [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(f_composed())
            ta.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(f_fused())
            tb.append(time.perf_counter() - t0)
        return float(np.median(ta) * 1e6), float(np.median(tb) * 1e6)

    for K in (10, 32):
        P = jnp.asarray(
            np.random.RandomState(0).rand(K, spec.n), jnp.float32
        )
        steps = jnp.arange(K, dtype=jnp.uint32)
        f_st = jax.jit(lambda P_, s_: sample_mask_st_hash(
            P_, spec.seed, spec.tensor_id, s_))
        f_draw = jax.jit(lambda P_, s_: sample_mask_hash(
            P_, spec.seed, spec.tensor_id, s_))
        f_rec = jax.jit(lambda Z_: ops.reconstruct_batched(spec, Z_))
        f_pack = jax.jit(pack_mask)
        f_fused = jax.jit(lambda P_, s_: ops.sample_reconstruct_batched(
            spec, P_, s_))
        f_spack = jax.jit(lambda P_, s_: ops.sample_pack_batched(
            spec, P_, s_))
        # bit-exactness gate before timing (fused == composed, exact)
        np.testing.assert_array_equal(
            np.asarray(f_fused(P, steps)),
            np.asarray(f_rec(f_draw(P, steps))),
            err_msg="fused forward not bit-exact vs composed",
        )
        np.testing.assert_array_equal(
            np.asarray(f_spack(P, steps)),
            np.asarray(f_pack(f_draw(P, steps))),
            err_msg="fused pack not bit-exact vs composed",
        )
        out = {"bench": "fused_mask_lifecycle", "K": K, "m": spec.m,
               "n": spec.n, "d": spec.d}
        out["fwd_composed_us"], out["fwd_fused_us"] = ab(
            lambda: f_rec(f_st(P, steps)), lambda: f_fused(P, steps))
        out["pack_composed_us"], out["pack_fused_us"] = ab(
            lambda: f_pack(f_draw(P, steps)), lambda: f_spack(P, steps))
        out["fwd_speedup"] = out["fwd_composed_us"] / out["fwd_fused_us"]
        out["pack_speedup"] = out["pack_composed_us"] / out["pack_fused_us"]
        out["lifecycle_speedup"] = (
            out["fwd_composed_us"] + out["pack_composed_us"]
        ) / (out["fwd_fused_us"] + out["pack_fused_us"])
        _emit(f"fused_lifecycle_K{K}", out["fwd_fused_us"],
              f"composed={out['fwd_composed_us']:.0f}us"
              f";fwd_speedup={out['fwd_speedup']:.3f}x"
              f";pack_speedup={out['pack_speedup']:.2f}x"
              f";lifecycle={out['lifecycle_speedup']:.3f}x")
        rows.append(out)
    return rows


def bench_downlink(full=False):
    """Downlink codec subsystem (this PR's tentpole): a real federated
    round per registered codec with the ENCODED scores as the carried
    state, reporting metered downlink bytes and round wall-clock.

    Bit-exactness asserted pre-timing: (a) the ``f32`` codec is the
    identity oracle — its encode returns the input arrays unchanged,
    so those rounds are bit-identical to the pre-codec protocol; (b)
    for the quantized codecs the widened-threshold integer draw equals
    the f32 draw on the decoded probabilities EXACTLY
    (``sample_mask_qhash`` vs ``sample_mask_hash``), and a round fed
    the u8 carry runs the vmap path to finite loss.

    Byte columns are MASK-ONLY (``score_downlink_bytes``, symmetric
    with bench_wire's ``mask_uplink_bytes``): u8 is exactly 1/4 of
    f32 per coordinate — the ci.sh gate requires <= 1/4.  Rows land in
    BENCH_reconstruct.json keyed (bench, K, strategy=codec).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.downlink import codec_names, get_codec
    from repro.comm.metering import score_downlink_bytes
    from repro.core import (
        FederatedConfig, ZamplingConfig, build_specs, encode_state,
        init_state,
    )
    from repro.core.federated import federated_round
    from repro.core.qspec import make_qspec
    from repro.core.sampling import sample_mask_hash, sample_mask_qhash
    from repro.data import client_batch_stream, iid_client_split, make_teacher_dataset
    from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss

    # draw-word exactness gate (quantized codecs), before any timing
    spec = make_qspec(0, (256, 256), 256, compression=8, d=8, window=128)
    rng = np.random.RandomState(0)
    for name in codec_names(include_aliases=False):
        codec = get_codec(name)
        if not codec.quantized:
            p = jnp.asarray(rng.rand(spec.n), jnp.float32)
            out = codec.encode(spec, p, jnp.uint32(3))
            np.testing.assert_array_equal(np.asarray(out), np.asarray(p))
            continue
        q = jnp.asarray(rng.randint(0, 1 << codec.bits, spec.n), jnp.uint32)
        if codec.packed:
            # packed codecs carry uint32 LANES: decode from the lanes,
            # draw from the per-coordinate words they unpack to
            from repro.comm.bitpack import pack_words

            wire = pack_words(q, codec.bits)
        else:
            wire = q.astype(codec.wire_dtype)
            q = wire.astype(jnp.uint32)
        a = np.asarray(sample_mask_qhash(q, codec.bits, spec.seed,
                                         spec.tensor_id, jnp.uint32(9)))
        b = np.asarray(sample_mask_hash(codec.decode(spec, wire), spec.seed,
                                        spec.tensor_id, jnp.uint32(9)))
        np.testing.assert_array_equal(
            a, b, err_msg=f"{name} integer draw not bit-exact vs decoded f32"
        )

    ds = make_teacher_dataset(n_train=2000, n_test=200, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=8.0, d=10, window=128, min_size=128))
    state0 = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    n = zspecs.n_total
    f32_down = score_downlink_bytes(get_codec("f32"), n)
    rows = []
    for K in (10, 32):
        clients = iid_client_split(ds, K)
        xs, ys = next(client_batch_stream(clients, 64, 2, seed=0))
        batch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
        for name in codec_names(include_aliases=False):
            codec = get_codec(name)
            cfg = FederatedConfig(num_clients=K, local_steps=2,
                                  local_lr=0.5, aggregate="psum_u32",
                                  downlink=name)
            st = encode_state(zspecs, cfg, state0)
            f = jax.jit(lambda s, b, k, cfg=cfg: federated_round(
                zspecs, s, mlp_loss, b, k, cfg))
            st1, met = f(st, batch, jax.random.PRNGKey(0))
            jax.block_until_ready(st1)
            assert np.isfinite(float(met["loss"])), name
            iters = 10 if full else 3
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(f(st, batch, jax.random.PRNGKey(0)))
            us = (time.perf_counter() - t0) / iters * 1e6
            down = score_downlink_bytes(codec, n)
            rows.append({
                "bench": "downlink_codec", "codec": name,
                "strategy": name, "K": K, "n": n, "us": us,
                "downlink_bytes_per_client": down,
                "downlink_vs_f32": down / f32_down,
            })
            _emit(f"downlink_codec_{name}_K{K}", us,
                  f"down={down}B;vs_f32={down / f32_down:.4f}")

    # adaptive rate schedules: a scanned R-round fit per schedule with
    # the REALIZED metered bytes (scheduled width + lane padding), one
    # compile each — ci.sh gates on these rows being present
    from repro.train import federated_fit

    K, R = 10, 4 if not full else 8
    clients = iid_client_split(ds, K)
    stream = client_batch_stream(clients, 64, 2, seed=0)
    per_round = [next(stream) for _ in range(R)]
    rb = {"x": jnp.asarray(np.stack([x for x, _ in per_round])),
          "y": jnp.asarray(np.stack([y for _, y in per_round]))}
    for sched, name in (("constant", "u8"), ("cosine", "packed4"),
                        ("frontier", "u8"), ("frontier", "packed4")):
        extra = {"downlink_schedule": sched, "schedule_b_min": 2}
        if sched == "cosine":
            extra["schedule_rounds"] = R
        cfg = FederatedConfig(num_clients=K, local_steps=2, local_lr=0.5,
                              aggregate="psum_u32", downlink=name, **extra)
        st = encode_state(zspecs, cfg, state0)
        f = jax.jit(lambda s, b, k, cfg=cfg: federated_fit(
            zspecs, s, mlp_loss, b, k, cfg))
        st1, met = f(st, rb, jax.random.PRNGKey(0))
        jax.block_until_ready(st1)
        assert np.isfinite(np.asarray(met["loss"])).all(), (sched, name)
        iters = 5 if full else 2
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(f(st, rb, jax.random.PRNGKey(0)))
        us = (time.perf_counter() - t0) / iters / R * 1e6
        down = np.asarray(met["downlink_bytes_per_client"], np.float64)
        rows.append({
            "bench": "downlink_schedule", "codec": name,
            "strategy": f"{sched}_{name}", "K": K, "n": n,
            "rounds": R, "us": us,
            "downlink_bytes_per_client": float(down[-1]),
            "downlink_bytes_cumulative": float(down.sum()),
            "downlink_vs_f32": float(down[-1]) / f32_down,
        })
        _emit(f"downlink_schedule_{sched}_{name}", us,
              f"cum={down.sum():.0f}B;last={down[-1]:.0f}B")
    return rows


def bench_faults(full=False):
    """Fault-tolerant partial-participation round engine (this PR's
    tentpole): full federated rounds through the weighted-aggregation
    path at dropout rates {0, 0.2, 0.5} vs the plain PR-5 protocol.

    Bit-exactness asserted PRE-TIMING: the zero-fault participation
    round (every client at weight 1, an all-zero FaultPlan) must
    reproduce the plain round's aggregated scores and loss bit for
    bit at each K.  ``fault_overhead`` is the zero-fault round's
    wall-clock over the plain round's (alternating-run medians) — the
    price of carrying fault draws, upload checksums, and weighted
    psums through a round nothing goes wrong in; scripts/ci.sh fails
    if the committed baseline shows > 1.05x.  Rows land in
    BENCH_reconstruct.json keyed (bench, K, strategy=dropout level).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        FederatedConfig, ZamplingConfig, build_specs, init_state,
    )
    from repro.core.federated import federated_round
    from repro.data import client_batch_stream, iid_client_split, make_teacher_dataset
    from repro.fault import FaultPlan
    from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss

    ds = make_teacher_dataset(n_train=2000, n_test=200, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=8.0, d=10, window=128, min_size=128))
    state0 = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    rows = []
    for K in (10, 32):
        clients = iid_client_split(ds, K)
        xs, ys = next(client_batch_stream(clients, 64, 2, seed=0))
        batch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
        cfg = FederatedConfig(num_clients=K, local_steps=2, local_lr=0.5,
                              aggregate="psum_u32")
        key = jax.random.PRNGKey(0)
        ids = jnp.arange(K, dtype=jnp.uint32)
        ones = jnp.ones(K, jnp.uint32)
        f_plain = jax.jit(lambda s, b, k, cfg=cfg: federated_round(
            zspecs, s, mlp_loss, b, k, cfg))
        for p in (0.0, 0.2, 0.5):
            plan = FaultPlan(dropout=p)
            f_fault = jax.jit(
                lambda s, b, k, cfg=cfg, plan=plan: federated_round(
                    zspecs, s, mlp_loss, b, k, cfg, client_ids=ids,
                    weights=ones, faults=plan))
            st_f, met = f_fault(state0, batch, key)
            jax.block_until_ready(st_f)
            assert np.isfinite(float(met["loss"]))
            if p == 0.0:
                # the acceptance gate, before any timing: zero faults
                # == the plain protocol, bit for bit
                st_p, met_p = f_plain(state0, batch, key)
                for path in st_p["scores"]:
                    np.testing.assert_array_equal(
                        np.asarray(st_p["scores"][path]),
                        np.asarray(st_f["scores"][path]),
                        err_msg=f"zero-fault scores diverge at {path}",
                    )
                assert (np.float32(met_p["loss"]).view(np.uint32)
                        == np.float32(met["loss"]).view(np.uint32)), \
                    "zero-fault loss not bit-identical to the plain round"
            iters = 20 if full else 8
            us_fault, us_plain = _ab_median(
                lambda: f_fault(state0, batch, key),
                lambda: f_plain(state0, batch, key), iters)
            rows.append({
                "bench": "fault_round", "strategy": f"dropout{p:g}",
                "K": K, "n": zspecs.n_total, "dropout": p,
                "us": us_fault, "plain_us": us_plain,
                "fault_overhead": us_fault / us_plain,
                "num_participating": float(met["num_participating"]),
            })
            _emit(f"fault_round_dropout{p:g}_K{K}", us_fault,
                  f"plain={us_plain:.0f}us"
                  f";overhead={us_fault / us_plain:.3f}x"
                  f";part={float(met['num_participating']):.0f}/{K}")
    return rows


def _device_peak_bytes():
    """Peak device memory if the backend reports it (GPU/TPU
    ``memory_stats``); ``None`` on CPU, whose allocations go through
    the host allocator and are invisible to XLA's stats."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend without stats support
        return None
    if not stats:
        return None
    return stats.get("peak_bytes_in_use")


def bench_streaming(full=False):
    """Streaming cohort accumulator vs the one-shot slab round (this
    PR's tentpole): identical federated rounds with
    ``stream_chunk=c`` folding uploads c clients at a time vs the
    (K, lanes) slab aggregation, K swept to 256.

    Bit-exactness asserted PRE-TIMING at every (K, chunk): the
    streaming round's aggregated scores must equal the slab round's
    bit for bit (uint32 vote counts are associative, so chunked
    folding changes nothing).  ``stream_overhead`` is the streaming
    round's wall-clock over the slab round's (alternating-run
    medians); scripts/ci.sh fails if the committed baseline shows
    > 1.05x at small K.  The memory columns are the analytic model
    (comm.metering): ``peak_upload_bytes`` — one chunk's lanes plus
    the (n,) vote accumulator — is a function of the CHUNK only and
    stays flat as K grows, while ``slab_upload_bytes`` grows linearly;
    at K=256/chunk=8 the slab holds 32x the lanes.  ``device_peak
    _bytes`` records the backend's measured peak where the platform
    reports one (GPU/TPU; None on CPU).  Rows land in
    BENCH_reconstruct.json keyed (bench, K, strategy=chunk level).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.metering import streaming_peak_bytes, upload_slab_bytes
    from repro.core import (
        FederatedConfig, ZamplingConfig, build_specs, init_state,
    )
    from repro.core.federated import federated_round
    from repro.data import make_teacher_dataset
    from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_loss

    ds = make_teacher_dataset(n_train=2000, n_test=200, seed=0)
    template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
    zspecs = build_specs(template, ZamplingConfig(
        compression=8.0, d=10, window=128, min_size=128))
    state0 = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    E, B = 2, 16
    rng = np.random.RandomState(0)
    rows = []
    # chunk divides K in every timed row: padding the last chunk would
    # bill the streaming side for wasted local updates and muddy the
    # pure folding-overhead number the CI gate pins
    for K, chunk in ((10, 5), (32, 8), (128, 8), (128, 32),
                     (256, 8), (256, 32)):
        idx = rng.randint(0, len(ds.x_train), (K, E, B))
        batch = {"x": jnp.asarray(ds.x_train[idx]),
                 "y": jnp.asarray(ds.y_train[idx])}
        key = jax.random.PRNGKey(0)
        cfg_slab = FederatedConfig(num_clients=K, local_steps=E,
                                   local_lr=0.5, aggregate="psum_u32")
        cfg_strm = FederatedConfig(num_clients=K, local_steps=E,
                                   local_lr=0.5, aggregate="psum_u32",
                                   stream_chunk=chunk)
        f_slab = jax.jit(lambda s, b, k, cfg=cfg_slab: federated_round(
            zspecs, s, mlp_loss, b, k, cfg))
        f_strm = jax.jit(lambda s, b, k, cfg=cfg_strm: federated_round(
            zspecs, s, mlp_loss, b, k, cfg))
        st_a, met_a = f_slab(state0, batch, key)
        st_b, met_b = f_strm(state0, batch, key)
        jax.block_until_ready((st_a, st_b))
        # the acceptance gate, before any timing: chunked folding ==
        # the slab aggregation, bit for bit
        for path in st_a["scores"]:
            np.testing.assert_array_equal(
                np.asarray(st_a["scores"][path]),
                np.asarray(st_b["scores"][path]),
                err_msg=f"streaming scores diverge at {path} "
                        f"(K={K}, chunk={chunk})",
            )
        assert np.isfinite(float(met_b["loss"]))
        iters = (20 if full else 8) if K <= 32 else (10 if full else 4)
        us_strm, us_slab = _ab_median(
            lambda: f_strm(state0, batch, key),
            lambda: f_slab(state0, batch, key), iters)
        peak = streaming_peak_bytes(zspecs, "psum_u32", chunk)
        slab = upload_slab_bytes(zspecs, "psum_u32", K)
        rows.append({
            "bench": "streaming_round", "strategy": f"chunk{chunk}",
            "K": K, "n": zspecs.n_total, "chunk": chunk,
            "us": us_strm, "slab_us": us_slab,
            "stream_overhead": us_strm / us_slab,
            "peak_upload_bytes": peak,
            "slab_upload_bytes": slab,
            "slab_vs_peak": slab / peak,
            "lane_ratio": slab / upload_slab_bytes(zspecs, "psum_u32",
                                                   chunk),
            "device_peak_bytes": _device_peak_bytes(),
        })
        _emit(f"streaming_round_K{K}_chunk{chunk}", us_strm,
              f"slab={us_slab:.0f}us"
              f";overhead={us_strm / us_slab:.3f}x"
              f";peak={peak / 1024:.0f}KiB"
              f";slab_mem={slab / 1024:.0f}KiB"
              f";slab_vs_peak={slab / peak:.1f}x")
    return rows


def _ab_median(f_a, f_b, iters):
    """Median us of each side, alternating runs (load drift cancels)."""
    import jax
    import numpy as np

    jax.block_until_ready(f_a())  # compile + warm
    jax.block_until_ready(f_b())
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f_a())
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(f_b())
        tb.append(time.perf_counter() - t0)
    return float(np.median(ta) * 1e6), float(np.median(tb) * 1e6)


class _env:
    """Temporarily set/unset an env var (trace-time knobs)."""

    def __init__(self, name, value):
        self.name, self.value = name, value

    def __enter__(self):
        self.prev = os.environ.get(self.name)
        if self.value is None:
            os.environ.pop(self.name, None)
        else:
            os.environ[self.name] = str(self.value)

    def __exit__(self, *exc):
        if self.prev is None:
            os.environ.pop(self.name, None)
        else:
            os.environ[self.name] = self.prev


def bench_bwd(full=False):
    """Transpose-plan backward vs the scatter oracle (this PR's
    tentpole): ``grad_Z = Q^T grad_W`` through the full custom_vjp
    chain at the bench spec (m=2^20, d=8), K clients, CPU ref path.

    The two paths are traced under their ``REPRO_BWD_PLAN`` gate (read
    at trace time; fresh closures -> fresh traces) and timed
    INTERLEAVED; allclose plan-vs-scatter is asserted before timing.

    ``scatter_bwd_us`` / ``plan_bwd_us`` time the PURE backward (the
    ``_bwd_many`` dispatch the custom_vjp invokes) so ``bwd_speedup``
    is not diluted by the shared forward that ``jax.grad`` would also
    evaluate; ``grad_scatter_us`` / ``grad_plan_us`` keep the full
    fwd+bwd grad-chain numbers for continuity with the PR-1
    ``federated_round_reconstruct`` *_bwd_us baseline rows.  Rows land
    in BENCH_reconstruct.json as ``bwd_transpose_plan`` keyed
    (bench, K); scripts/ci.sh requires them and fails if the plan
    path's ``bwd_speedup`` regresses below 1.0.
    """
    import functools as _ft

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.qspec import make_qspec
    from repro.kernels import ops

    spec = make_qspec(0, (1024, 1024), 1024, compression=32, d=8, window=512)
    rows = []
    for K in (4, 10, 32):
        Z = jnp.asarray(
            (np.random.RandomState(0).rand(K, spec.n) < 0.5), jnp.float32
        )
        V = jnp.asarray(
            np.random.RandomState(1).randn(K, *spec.shape), jnp.float32
        )

        def make_bwd():
            # the exact transpose dispatch the custom_vjp bwd invokes;
            # a fresh closure per gate: the trace re-reads REPRO_BWD_PLAN
            return jax.jit(
                lambda G_: ops._bwd_many(spec, G_, "ref", 1, None)
            )

        def make_grad():
            g = jax.jit(jax.grad(
                lambda Z_, v: jnp.vdot(ops.reconstruct_batched(spec, Z_),
                                       v)
            ))
            return _ft.partial(g, v=V)

        with _env("REPRO_BWD_PLAN", "scatter"):
            b_scatter, g_scatter = make_bwd(), make_grad()
            # compile INSIDE the gate block: jit traces (and reads the
            # env) at first call, not at wrapper creation
            out_scatter = np.asarray(b_scatter(V))
            jax.block_until_ready(g_scatter(Z))
        with _env("REPRO_BWD_PLAN", "plan"):
            b_plan, g_plan = make_bwd(), make_grad()
            out_plan = np.asarray(b_plan(V))
            jax.block_until_ready(g_plan(Z))
            f_fwd = jax.jit(lambda Z_: ops.reconstruct_batched(spec, Z_))
            jax.block_until_ready(f_fwd(Z))
        np.testing.assert_allclose(out_plan, out_scatter, rtol=1e-4,
                                   atol=1e-4)
        iters = 10 if full else 3
        scatter_us, plan_us = _ab_median(
            lambda: b_scatter(V), lambda: b_plan(V), iters)
        grad_scatter_us, grad_plan_us = _ab_median(
            lambda: g_scatter(Z), lambda: g_plan(Z), iters)
        f_fwd(Z).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            f_fwd(Z).block_until_ready()
        fwd_us = (time.perf_counter() - t0) / iters * 1e6
        out = {
            "bench": "bwd_transpose_plan", "K": K, "m": spec.m,
            "n": spec.n, "d": spec.d,
            "scatter_bwd_us": scatter_us, "plan_bwd_us": plan_us,
            "bwd_speedup": scatter_us / plan_us,
            "grad_scatter_us": grad_scatter_us,
            "grad_plan_us": grad_plan_us,
            "grad_speedup": grad_scatter_us / grad_plan_us,
            "fwd_us": fwd_us, "bwd_fwd_ratio_plan": plan_us / fwd_us,
        }
        _emit(f"bwd_transpose_plan_K{K}", plan_us,
              f"scatter={scatter_us:.0f}us"
              f";bwd_speedup={out['bwd_speedup']:.2f}x"
              f";grad_speedup={out['grad_speedup']:.2f}x"
              f";bwd:fwd={out['bwd_fwd_ratio_plan']:.2f}")
        rows.append(out)
    return rows


def bench_threshold(full=False):
    """Re-measure the ``REPRO_BATCH_MAP_THRESHOLD`` crossover (ROADMAP
    open item) now that the backward no longer dominates: force each
    batched contraction strategy via the env var across spec sizes
    spanning the default threshold (m_pad·d = 2e6) and time fwd and
    the (plan) bwd.  The threshold also gates the plan backward's
    lax.map-vs-broadcast choice, so both directions are reported.
    Rows keyed (bench, K, strategy, m_pad_d) in BENCH_reconstruct.json.
    """
    import functools as _ft

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.qspec import make_qspec
    from repro.kernels import ops

    K = 10
    rows = []
    for shape in ((256, 256), (512, 512), (1024, 1024)):
        spec = make_qspec(0, shape, shape[0], compression=32, d=8,
                          window=512)
        Z = jnp.asarray(
            (np.random.RandomState(0).rand(K, spec.n) < 0.5), jnp.float32
        )
        V = jnp.asarray(
            np.random.RandomState(1).randn(K, *spec.shape), jnp.float32
        )
        for strategy, thresh in (("fused", 1 << 62), ("lax_map", 1)):
            with _env("REPRO_BATCH_MAP_THRESHOLD", thresh):
                f = jax.jit(lambda Z_: ops.reconstruct_batched(spec, Z_))
                g = _ft.partial(jax.jit(jax.grad(
                    lambda Z_, v: jnp.vdot(
                        ops.reconstruct_batched(spec, Z_), v)
                )), v=V)
                jax.block_until_ready(f(Z))
                jax.block_until_ready(g(Z))
                iters = 10 if full else 3
                t0 = time.perf_counter()
                for _ in range(iters):
                    f(Z).block_until_ready()
                fwd_us = (time.perf_counter() - t0) / iters * 1e6
                t0 = time.perf_counter()
                for _ in range(iters):
                    g(Z).block_until_ready()
                bwd_us = (time.perf_counter() - t0) / iters * 1e6
            rows.append({
                "bench": "batch_map_threshold", "K": K,
                "strategy": strategy, "m_pad_d": spec.m_pad * spec.d,
                "m": spec.m, "n": spec.n, "d": spec.d,
                "fwd_us": fwd_us, "bwd_us": bwd_us,
            })
            _emit(f"batch_map_threshold_{strategy}_mpd{spec.m_pad * spec.d}",
                  fwd_us, f"bwd={bwd_us:.0f}us;K={K}")
    return rows


def bench_table1(full=False):
    from repro.experiments import comm_savings_table

    t0 = time.perf_counter()
    rows = comm_savings_table()
    us = (time.perf_counter() - t0) * 1e6
    for r in rows:
        _emit("table1_comm_savings", us / len(rows),
              f"{r['method']}:client={r['client_savings']:.0f}x"
              f";server={r['server_savings']:.2f}x")
    return rows


def bench_table2(full=False):
    from repro.experiments import run_local_compression

    t0 = time.perf_counter()
    rows = run_local_compression(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("table2_compression", us,
              f"d={r['d']};m/n={r['compression']}"
              f";sampled={r['sampled_acc']:.3f}")
    return rows


def bench_fig4(full=False):
    from repro.experiments import run_federated

    t0 = time.perf_counter()
    rows = run_federated(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("fig4_federated", us,
              f"m/n={r['compression']};acc={r['final_sampled_acc']:.3f}"
              f";client_savings={r['client_savings']:.0f}x")
    return rows


def bench_table4(full=False):
    from repro.experiments import run_sensitivity

    t0 = time.perf_counter()
    rows = run_sensitivity(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("table4_sensitivity", us,
              f"{r['training']};tau={r['tau']}"
              f";sens={r['avg_sensitivity']:.4f}")
    return rows


def bench_fig5(full=False):
    from repro.experiments import run_integrality

    t0 = time.perf_counter()
    rows = run_integrality(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("fig5_integrality", us,
              f"beta={r['beta']};gap={r['integrality_gap']:.3f}")
    return rows


def bench_fig6(full=False):
    from repro.experiments import run_zhou_comparison

    t0 = time.perf_counter()
    rows = run_zhou_comparison(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("fig6_zhou", us,
              f"{r['method']};mean={r['mean_sampled_acc']:.3f}"
              f";best={r['best_mask_acc']:.3f}")
    return rows


def bench_roofline(full=False):
    """Roofline terms per (arch x shape) from the dry-run artifacts."""
    from benchmarks.roofline import summarize_dir

    rows = summarize_dir("experiments/dryrun")
    for r in rows:
        _emit("roofline", 0.0,
              f"{r['arch']}/{r['shape']}:bound={r['bound']}"
              f";t_comp={r['t_compute_ms']:.2f}ms"
              f";t_mem={r['t_memory_ms']:.2f}ms"
              f";t_coll={r['t_collective_ms']:.2f}ms")
    return rows


def bench_wire_formats(full=False):
    """The end-to-end wire-format table (experiments.run_wire_formats):
    a real federated round per transport, bit-exactness asserted."""
    from repro.experiments import run_wire_formats

    t0 = time.perf_counter()
    rows = run_wire_formats(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("wire_formats", us,
              f"{r['strategy']};up={r['uplink_bytes_per_client']:.0f}B"
              f";vs_f32={r['uplink_vs_f32']:.4f}")
    return rows


def bench_downlink_tradeoff(full=False):
    """Accuracy vs downlink bytes per codec — the paper's trade-off
    knob as a table (experiments.run_downlink_tradeoff)."""
    from repro.experiments import run_downlink_tradeoff

    t0 = time.perf_counter()
    rows = run_downlink_tradeoff(quick=not full)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    for r in rows:
        _emit("downlink_tradeoff", us,
              f"{r['codec']};acc={r['final_sampled_acc']:.3f}"
              f";down={r['downlink_bytes_per_client']:.0f}B"
              f";vs_f32={r['downlink_vs_f32']:.4f}")
    return rows


def bench_serve(full=False):
    """Zampling-native serving: dense vs reconstruct-on-load vs
    streaming (this PR's tentpole), plus the delta broadcast.

    ``serve_decode`` rows: tokens/sec and resident zampled-state bytes
    per serving mode at two model sizes.  Bit-exactness is asserted
    PRE-TIMING: streaming and load generations must agree bit for bit
    at every size (and per-step across all three downlink codecs at
    the small size) — the modes share the canonical serve contraction
    (kernels/ops.py), so the resident-bytes win carries zero output
    risk.  All timings are CPU; the streaming impl timed is 'chunked'
    (the jnp fallback) and the one interpret-mode Pallas row is keyed
    ``impl='u8_pallas_interpret'`` with ``regression_comparable:
    False`` (interpreter artifact, not kernel perf — same convention
    as kernel_qz_reconstruct; on a TPU it is the compiled
    ``u8_pallas`` row).  The dense row serves the SAME sampled
    weights through model.decode_step — the no-zampling baseline.

    ``serve_delta`` rows: exact delta-vs-full broadcast bytes on a
    converged-round scenario (1% of scores move, re-encoded under the
    SAME dither word per the comm/downlink.py reuse rule), one row per
    codec; asserts delta_bytes <= full_bytes / 8 AND that apply_delta
    on a live state reproduces the fresh round t+1 state bitwise.
    Rows land in BENCH_reconstruct.json keyed (bench, K=d_model,
    strategy=mode, impl=codec); scripts/ci.sh gates on the byte
    columns.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_arch
    from repro.core import ZamplingConfig, build_specs, init_state
    from repro.kernels import ops
    from repro.core.zampling import sample_weights
    from repro.models import build_model
    from repro.serve import (apply_delta, build_serve_engine, delta_report,
                             generate, make_delta, make_generator,
                             make_serve_state)

    small = get_arch("qwen2-0.5b").reduced()
    large = dataclasses.replace(small, name="qwen2-0.5b-r512",
                                d_model=512, d_ff=1024)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    new_tokens = 6 if full else 4
    B, Sp = prompt.shape
    seq_len = Sp + new_tokens
    rows = []

    for cfg in (small, large):
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        zspecs = build_specs(params, ZamplingConfig(compression=8, d=8,
                                                    min_size=2048))
        state = init_state(jax.random.PRNGKey(1), zspecs,
                           dense_init=params)
        sstate = make_serve_state(zspecs, state, jax.random.PRNGKey(2),
                                  downlink="u8")

        # bit-exactness oracle before any timing: streaming == load,
        # full generation; per-step across all codecs at small size
        outs = {}
        for mode in ("load", "streaming"):
            engine = build_serve_engine(model, sstate, mode=mode)
            run = make_generator(engine.step, new_tokens)
            toks, _ = run(engine.arrays_of(sstate),
                          engine.init_cache(B, seq_len), prompt,
                          jax.random.PRNGKey(0))
            outs[mode] = toks
        assert (outs["load"] == outs["streaming"]).all(), \
            f"serve modes diverge at d_model={cfg.d_model}"
        if cfg is small:
            for codec in ("f32", "u16", "u8"):
                ss = make_serve_state(zspecs, state,
                                      jax.random.PRNGKey(2),
                                      downlink=codec)
                es = build_serve_engine(model, ss, mode="streaming")
                el = build_serve_engine(model, ss, mode="load")
                c0 = es.init_cache(B, seq_len)
                ls, _ = jax.jit(es.step)(es.arrays_of(ss), c0,
                                         prompt[:, :1])
                ll, _ = jax.jit(el.step)(el.arrays_of(ss), c0,
                                         prompt[:, :1])
                assert (ls == ll).all(), f"codec {codec} diverges"

        # sampled dense weights = the same model a no-zampling fleet
        # would hold; serves through model.decode_step
        dense_params = sample_weights(zspecs, state, jax.random.PRNGKey(2))

        def _time(fn):
            fn()  # compile
            t0 = time.perf_counter()
            fn()
            return (time.perf_counter() - t0)

        zamp_bytes = {
            "dense": 4 * zspecs.m_total,
            "load": sstate.loaded_zampled_bytes(),
            "streaming": sstate.resident_zampled_bytes(),
        }
        for mode in ("dense", "load", "streaming"):
            if mode == "dense":
                dt = _time(lambda: generate(
                    model, dense_params, prompt, new_tokens,
                    seq_len=seq_len).block_until_ready())
            else:
                engine = build_serve_engine(model, sstate, mode=mode)
                arrays = engine.arrays_of(sstate)
                run = make_generator(engine.step, new_tokens)
                cache = engine.init_cache(B, seq_len)
                dt = _time(lambda: run(arrays, cache, prompt,
                                       jax.random.PRNGKey(0)
                                       )[0].block_until_ready())
            tok_s = B * new_tokens / dt
            rows.append({
                "bench": "serve_decode", "K": cfg.d_model,
                "strategy": mode,
                "impl": "dense" if mode == "dense" else "u8",
                "tok_s": tok_s, "us": dt / (B * new_tokens) * 1e6,
                "resident_zampled_bytes": zamp_bytes[mode],
                "dense_bytes": sstate.dense_bytes(),
                "m_total": zspecs.m_total, "n_total": zspecs.n_total,
                "bit_exact_vs_load": mode != "dense",
                "regression_comparable": True,
            })
            _emit(f"serve_decode_{mode}_d{cfg.d_model}",
                  dt / (B * new_tokens) * 1e6,
                  f"tok_s={tok_s:.2f}"
                  f";zampled_bytes={zamp_bytes[mode]}")

        if cfg is small:
            # one Pallas step; on the CPU it runs in the interpreter
            # (correctness-path timing only: the interpreter walks the
            # one-hot contraction), so that row is excluded from perf
            # regression comparisons
            interp = ops._interpret()
            engine = build_serve_engine(model, sstate, mode="streaming",
                                        impl="pallas")
            arrays = engine.arrays_of(sstate)
            cache = engine.init_cache(B, seq_len)
            stepf = jax.jit(engine.step)
            dt = _time(lambda: stepf(arrays, cache, prompt[:, :1]
                                     )[0].block_until_ready())
            rows.append({
                "bench": "serve_decode", "K": cfg.d_model,
                "strategy": "streaming",
                "impl": "u8_pallas_interpret" if interp else "u8_pallas",
                "tok_s": B / dt, "us": dt / B * 1e6,
                "resident_zampled_bytes": zamp_bytes["streaming"],
                "dense_bytes": sstate.dense_bytes(),
                "m_total": zspecs.m_total, "n_total": zspecs.n_total,
                "bit_exact_vs_load": True,
                "regression_comparable": not interp,
            })
            _emit(f"serve_decode_streaming_pallas_d{cfg.d_model}",
                  dt / B * 1e6,
                  "interpret-mode;not-comparable" if interp else "compiled")

    # --- delta broadcast on a converged round ----------------------------
    model = build_model(small)
    params = model.init_params(jax.random.PRNGKey(0))
    zspecs = build_specs(params, ZamplingConfig(compression=8, d=8,
                                                min_size=2048))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=params)
    key = jax.random.PRNGKey(7)
    scores2 = {}
    for p, s in state["scores"].items():
        k1, k2, key = jax.random.split(key, 3)
        touch = jax.random.bernoulli(k1, 0.01, s.shape)
        scores2[p] = jnp.where(
            touch, s + 0.05 * jax.random.normal(k2, s.shape), s)
    state2 = {"scores": scores2, "dense": state["dense"]}
    for codec in ("f32", "u16", "u8"):
        s1 = make_serve_state(zspecs, state, jax.random.PRNGKey(2),
                              downlink=codec, dither_word=0)
        s2 = make_serve_state(zspecs, state2, jax.random.PRNGKey(2),
                              downlink=codec, dither_word=0)
        swapped = apply_delta(s1, make_delta(s1, s2))
        assert all(bool((swapped.words[p] == s2.words[p]).all())
                   for p in s2.words), f"hot-swap != fresh load ({codec})"
        rep = delta_report(s1, s2)
        assert rep["delta_bytes"] < rep["full_bytes"], codec
        assert rep["delta_vs_full"] <= 0.125, \
            f"delta {rep['delta_vs_full']:.4f} > 1/8 ({codec})"
        rows.append({
            "bench": "serve_delta", "strategy": codec,
            "words_total": rep["words_total"],
            "words_changed": rep["words_changed"],
            "delta_bytes": rep["delta_bytes"],
            "full_bytes": rep["full_bytes"],
            "delta_vs_full": rep["delta_vs_full"],
            "changed_frac": 0.01,
            "regression_comparable": True,
        })
        _emit(f"serve_delta_{codec}", 0.0,
              f"delta={rep['delta_bytes']}B;full={rep['full_bytes']}B"
              f";ratio={rep['delta_vs_full']:.4f}")
    return rows


def bench_serve_throughput(full=False):
    """Continuous batching + the hot-block cache: tok/s vs batch width.

    ``serve_batch`` rows: tokens/sec at batch B in {1, 4, 16} x serving
    mode in {load, streaming, cached} on the reduced model (window=128
    specs so the retention scenario below is fine-grained).  The cached
    mode runs at FULL budget — the pool caps at one row per canonical
    tile, so this is the upper end of the dial; its budget and the
    exact resident bytes (comm.metering.serve_resident_bytes: words +
    pool + lane KV + dense) land in every row, along with the device
    peak probe (None on CPU).  Bit-exactness is asserted PRE-TIMING at
    every batch width: the three modes' generations must agree bit for
    bit, so the throughput column carries zero output risk.

    One ``strategy="scheduler"`` row drives the real continuous-batching
    scheduler (ragged prompts, admission/retirement, host-side greedy
    sampling) at the largest width — ``regression_comparable: False``,
    since its pacing includes the host control plane.

    One ``strategy="retention"`` row replays the converged-round
    scenario (1% of scores move, amp 0.02, pinned dither + draw words)
    against a fully warm cache: drawn-bit invalidation must retain
    >= 90% of the pool, asserted here and gated in scripts/ci.sh along
    with cached >= 2x streaming tok/s at the largest batch.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.metering import serve_resident_bytes
    from repro.configs.registry import get_arch
    from repro.core import ZamplingConfig, build_specs, init_state
    from repro.models import build_model
    from repro.serve import (HotBlockCache, ServeConfig, ServeScheduler,
                             apply_delta, build_serve_engine, make_delta,
                             make_generator, make_serve_state)

    cfg = get_arch("qwen2-0.5b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # d=12: the per-block regeneration the cache elides walks 12 edges
    # per row — the production-density regime, where streaming pays for
    # every decode step and the pool's gather does not
    zspecs = build_specs(params, ZamplingConfig(compression=4, d=12,
                                                window=128))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=params)
    sstate = make_serve_state(zspecs, state, jax.random.PRNGKey(2),
                              downlink="u8", dither_word=0)
    budget = 1 << 30  # >= model: pool caps at one row per tile
    cache = HotBlockCache(sstate, budget)
    cache.fill(sstate)
    assert cache.capacity_bytes <= budget

    Sp = 4
    new_tokens = 6 if full else 4
    seq_len = Sp + new_tokens
    batches = (1, 4, 16)
    allp = jnp.asarray(
        np.random.RandomState(0).randint(1, cfg.vocab, (max(batches), Sp)),
        jnp.int32)

    def _time(fn):
        fn()  # compile
        best = float("inf")
        for _ in range(3):  # min-of-3: the 2x CI gate needs low noise
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    rows = []
    for B in batches:
        prompt = allp[:B]
        outs, runs = {}, {}
        for mode in ("load", "streaming", "cached"):
            engine = build_serve_engine(model, sstate, mode=mode)
            arrays = engine.arrays_of(
                sstate, cache=cache if mode == "cached" else None)
            run = make_generator(engine.step, new_tokens)
            kv = engine.init_cache(B, seq_len)
            toks, _ = run(arrays, kv, prompt, jax.random.PRNGKey(0))
            outs[mode] = np.asarray(toks)
            runs[mode] = (run, arrays, kv)
        assert (outs["load"] == outs["streaming"]).all(), B
        assert (outs["load"] == outs["cached"]).all(), B
        for mode in ("load", "streaming", "cached"):
            run, arrays, kv = runs[mode]
            dt = _time(lambda: run(arrays, kv, prompt,
                                   jax.random.PRNGKey(0)
                                   )[0].block_until_ready())
            tok_s = B * new_tokens / dt
            res = serve_resident_bytes(
                sstate, budget if mode == "cached" else 0, mode=mode,
                kv_cache=kv)
            assert res["cache_bytes"] <= budget
            rows.append({
                "bench": "serve_batch", "K": B, "strategy": mode,
                "impl": "u8", "tok_s": tok_s,
                "us": dt / (B * new_tokens) * 1e6,
                "cache_budget_bytes": budget if mode == "cached" else 0,
                "resident_bytes": res["total_bytes"],
                "cache_bytes": res["cache_bytes"],
                "device_peak_bytes": _device_peak_bytes(),
                "bit_exact_across_modes": True,
                "regression_comparable": True,
            })
            _emit(f"serve_batch_{mode}_B{B}",
                  dt / (B * new_tokens) * 1e6,
                  f"tok_s={tok_s:.2f}"
                  f";resident={res['total_bytes']:.0f}B")

    # the real scheduler at the largest width: ragged prompts, lane
    # admission/retirement, host greedy sampling (not gate-comparable)
    lanes = max(batches)
    sched = ServeScheduler(model, sstate, ServeConfig(
        lanes=lanes, seq_len=seq_len, cache_budget_bytes=budget,
        mode="cached", max_new_tokens=new_tokens), cache=cache)
    ragged = [list(range(1, 2 + (i % Sp))) for i in range(2 * lanes)]
    for p in ragged:
        sched.submit(p)
    t0 = time.perf_counter()
    results = sched.run()
    dt = time.perf_counter() - t0
    ntok = sum(len(v) for v in results.values())
    rows.append({
        "bench": "serve_batch", "K": lanes, "strategy": "scheduler",
        "impl": "u8", "tok_s": ntok / dt, "us": dt / ntok * 1e6,
        "requests": len(ragged), "engine_steps": sched.metrics()["steps"],
        "cache_budget_bytes": budget,
        "device_peak_bytes": _device_peak_bytes(),
        "regression_comparable": False,  # includes compile + host pacing
    })
    _emit(f"serve_batch_scheduler_B{lanes}", dt / ntok * 1e6,
          f"tok_s={ntok / dt:.2f};requests={len(ragged)};incl-compile")

    # cache retention across a converged round's delta hot-swap
    key = jax.random.PRNGKey(7)
    scores2 = {}
    for p, s in state["scores"].items():
        k1, k2, key = jax.random.split(key, 3)
        touch = jax.random.bernoulli(k1, 0.01, s.shape)
        scores2[p] = jnp.where(
            touch, s + 0.02 * jax.random.normal(k2, s.shape), s)
    s2 = make_serve_state(zspecs, {"scores": scores2,
                                   "dense": state["dense"]},
                          jax.random.PRNGKey(2), downlink="u8",
                          dither_word=0)
    cache.fill(sstate)  # re-warm after the scheduler run
    total = cache.resident_tiles
    assert total == cache.total_tiles
    apply_delta(sstate, make_delta(sstate, s2), cache=cache)
    retained = cache.resident_tiles / total
    assert retained >= 0.9, f"cache retention {retained:.3f} < 0.9"
    rows.append({
        "bench": "serve_batch", "strategy": "retention", "impl": "u8",
        "total_tiles": total, "retained_tiles": cache.resident_tiles,
        "retained_fraction": retained, "changed_frac": 0.01,
        "amp": 0.02, "window": 128,
        "regression_comparable": True,
    })
    _emit("serve_batch_retention", 0.0,
          f"retained={cache.resident_tiles}/{total}"
          f";fraction={retained:.4f}")
    return rows


BENCHES = {
    "kernel": lambda full: bench_kernel_reconstruct(),
    "fedround": bench_federated_round,
    "fused": bench_fused,
    "bwd": bench_bwd,
    "threshold": bench_threshold,
    "wire": bench_wire,
    "downlink": bench_downlink,
    "faults": bench_faults,
    "streaming": bench_streaming,
    "serve": bench_serve,
    "serve_batch": bench_serve_throughput,
    "wire_formats": bench_wire_formats,
    "downlink_tradeoff": bench_downlink_tradeoff,
    "table1": bench_table1,
    "table2": bench_table2,
    "fig4": bench_fig4,
    "table4": bench_table4,
    "fig5": bench_fig5,
    "fig6": bench_fig6,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    failed = []
    for name in only:
        try:
            rows = BENCHES[name](args.full)
            _dump(name, rows)
            if name in ("kernel", "fedround", "fused", "bwd", "threshold",
                        "wire", "downlink", "faults", "streaming",
                        "serve", "serve_batch"):
                _merge_bench_root(rows)
        except Exception as e:  # noqa: BLE001
            _emit(name, 0.0, f"ERROR:{e}")
            failed.append(name)
    if failed:  # make scripts/ci.sh a real gate (exit non-zero)
        sys.exit(f"benchmarks failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
