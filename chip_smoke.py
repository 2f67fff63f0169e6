"""Smoke check: the main paths run on one TPU chip with compiled kernels.

  python chip_smoke.py                # one chip: train + serve phases
  python chip_smoke.py --four-chips   # four chips: the sharded fit only

One process drives every phase.  Each phase prints one line — its
name, trace, compile and run seconds, and the numbers it checked — and
a failed check or a phase error ends the script non-zero.  On a TPU the
last line is ``{"ok": true, "device": {...}}``.  On any other backend
the phases run at a tiny size as a rehearsal (Pallas in interpret
mode) and the script exits non-zero without that line.

Phases (one chip):
 - train: federated MNIST-FC (784-300-100-10) at m/n = 8, d = 10,
   window 128, K = 10 clients, E = 10 local steps of batch 64,
   psum_u32 uplink, u8 downlink, on the seeded teacher dataset: 3
   rounds of jitted ``federated_fit`` with impl='ref' and with
   impl='pallas' (whose compiled round must hold ``tpu_custom_call``);
 - train_compare: from one shared state, the two impls' upload lanes
   (exact), reconstructed weights, and one round's new scores and
   dense leaves (both within 1e-5), at full f32 matmul precision;
 - serve: a ``ServeScheduler`` over the reduced qwen2-0.5b preset with a
   u8 carry in streaming mode serves 4 requests with impl='chunked'
   and impl='pallas'; both must emit the same tokens.

``--four-chips``: 3 rounds of ``sharded_client_fit`` (one client per
device, psum_u32) on a (4,) 'data' mesh against ``federated_fit`` at
K = 4 on one chip; the scores must be bitwise equal.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TOL = 1e-5


class CheckFailed(Exception):
    pass


def check(ok, what):
    if not ok:
        raise CheckFailed(what)


def phase_line(name, times, **checked):
    """One phase's line: ``times`` is (trace_s, compile_s, run_s) —
    tracing + lowering, the XLA compile (a persistent-cache hit makes it
    short), and the first run to ``block_until_ready``."""
    trace_s, compile_s, run_s = times
    fields = " ".join(f"{k}={v}" for k, v in checked.items())
    print(f"phase={name} trace_s={trace_s:.2f} compile_s={compile_s:.2f} "
          f"run_s={run_s:.2f} {fields}", flush=True)


def compile_and_run(fn, *args):
    """(compiled, outputs, times) of ``jax.jit(fn)(*args)``, with times
    as ``phase_line`` takes them."""
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return compiled, out, (t1 - t0, t2 - t1, time.perf_counter() - t2)


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def max_diff(a, b) -> float:
    import jax
    import numpy as np

    diffs = jax.tree.map(
        lambda x, y: float(np.max(np.abs(np.asarray(x, np.float64)
                                         - np.asarray(y, np.float64)),
                                  initial=0.0)), a, b)
    return max(jax.tree.leaves(diffs), default=0.0)


# ---------------------------------------------------------------------------
# federated training (MNIST-FC)
# ---------------------------------------------------------------------------

def train_setup(dims, clients, local_steps, rounds, batch):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (FederatedConfig, ZamplingConfig, build_specs,
                            encode_state, init_state)
    from repro.data import (client_batch_stream, iid_client_split,
                            make_teacher_dataset)
    from repro.models.mlp import init_mlp_params

    template = init_mlp_params(jax.random.PRNGKey(0), dims)
    zspecs = build_specs(template, ZamplingConfig(
        compression=8, d=10, window=128, min_size=128))
    cfg = FederatedConfig(num_clients=clients, local_steps=local_steps,
                          local_lr=0.5, aggregate="psum_u32",
                          downlink="u8")
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
    state = encode_state(zspecs, cfg, state)
    ds = make_teacher_dataset(n_train=clients * 400, n_test=200, seed=0)
    stream = client_batch_stream(iid_client_split(ds, clients), batch,
                                 local_steps, seed=0)
    xs, ys = zip(*(next(stream) for _ in range(rounds)))
    batches = {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))}
    return zspecs, cfg, state, batches


def phase_train(size):
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.models.mlp import mlp_loss
    from repro.train import federated_fit

    zspecs, cfg, state, batches = train_setup(**size)
    key = jax.random.PRNGKey(7)
    on_tpu = jax.devices()[0].platform == "tpu"
    for impl in ("ref", "pallas"):
        ops.set_default_impl(impl)  # read when the round is traced
        try:
            compiled, (_, mets), times = compile_and_run(
                lambda s, b, k: federated_fit(zspecs, s, mlp_loss, b, k, cfg),
                state, batches, key)
        finally:
            ops.set_default_impl("ref")
        losses = np.asarray(mets["loss"])
        kernel = has_kernel(compiled)
        phase_line(f"train[{impl}]", times,
                   rounds=losses.size, loss=[round(float(x), 4) for x in losses],
                   tpu_custom_call=kernel)
        check(np.isfinite(losses).all(), f"train[{impl}] loss not finite")
        if on_tpu:
            check(kernel == (impl == "pallas"),
                  f"train[{impl}] tpu_custom_call={kernel}")
    return zspecs, cfg, state, batches


def phase_train_compare(zspecs, cfg, state, batches):
    """One round from one shared state, ref against pallas."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comm.downlink import get_codec
    from repro.core.federated import federated_round
    from repro.kernels import ops
    from repro.models.mlp import mlp_loss

    codec = get_codec(cfg.downlink)
    k = cfg.num_clients
    steps = jnp.arange(k, dtype=jnp.uint32) + 7
    probs = {p: jnp.broadcast_to(jnp.clip(codec.decode(s, state["scores"][p]),
                                          0.0, 1.0), (k, s.n))
             for p, s in zspecs.specs.items()}
    batch = jax.tree.map(lambda x: x[0], batches)
    key = jax.random.split(jax.random.PRNGKey(7), 3)[0]

    def ops_round(impl):
        def fn(state, batch):
            lanes, weights = {}, {}
            for p, s in zspecs.specs.items():
                lanes[p] = ops.sample_pack_batched(s, probs[p], steps,
                                                   impl=impl)
                weights[p] = ops.sample_reconstruct_batched(
                    s, probs[p], steps, impl=impl)
            ops.set_default_impl(impl)
            try:
                new, mets = federated_round(zspecs, state, mlp_loss, batch,
                                            key, cfg, round_index=0)
            finally:
                ops.set_default_impl("ref")
            return lanes, weights, new, mets["loss"]
        return fn

    out, times = {}, (0.0, 0.0, 0.0)
    with jax.default_matmul_precision("highest"):
        for impl in ("ref", "pallas"):
            _, out[impl], t = compile_and_run(ops_round(impl), state, batch)
            times = tuple(a + b for a, b in zip(times, t))
    (lr, wr, nr, loss_r), (lp, wp, np_, loss_p) = out["ref"], out["pallas"]
    lanes_equal = all(np.array_equal(np.asarray(lr[p]), np.asarray(lp[p]))
                      for p in lr)
    dw = max_diff(wr, wp)
    decode = {p: codec.decode(s, nr["scores"][p]) for p, s in
              zspecs.specs.items()}
    decode_p = {p: codec.decode(s, np_["scores"][p]) for p, s in
                zspecs.specs.items()}
    ds = max_diff(decode, decode_p)
    dd = max_diff(nr["dense"], np_["dense"])
    phase_line("train_compare", times, upload_lanes_equal=lanes_equal,
               max_abs_dw=dw, max_abs_dscore=ds, max_abs_ddense=dd,
               loss_ref=float(loss_r), loss_pallas=float(loss_p))
    check(lanes_equal, "upload lanes differ between ref and pallas")
    check(dw <= TOL, f"reconstructed weights differ by {dw}")
    check(ds <= TOL, f"new scores differ by {ds}")
    check(dd <= TOL, f"new dense leaves differ by {dd}")
    check(np.isfinite([float(loss_r), float(loss_p)]).all(),
          "round loss not finite")


# ---------------------------------------------------------------------------
# serving (reduced qwen2-0.5b, u8 carry, streaming)
# ---------------------------------------------------------------------------

def phase_serve(prompt_len, new_tokens):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.core import ZamplingConfig, build_specs, init_state
    from repro.models import build_model
    from repro.serve import ServeConfig, ServeScheduler, make_serve_state

    arch = get_arch("qwen2-0.5b").reduced()
    model = build_model(arch)
    params = model.init_params(jax.random.PRNGKey(0))
    zspecs = build_specs(params, ZamplingConfig(compression=4, d=12,
                                                window=128))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=params)
    sstate = make_serve_state(zspecs, state, jax.random.PRNGKey(2),
                              downlink="u8", dither_word=0)
    prompts = np.random.RandomState(0).randint(1, arch.vocab,
                                               (4, prompt_len))
    on_tpu = jax.devices()[0].platform == "tpu"
    tokens = {}
    for impl in ("chunked", "pallas"):
        sched = ServeScheduler(model, sstate, ServeConfig(
            lanes=4, seq_len=prompt_len + new_tokens, mode="streaming",
            impl=impl, max_new_tokens=new_tokens))
        lanes = sched.config.lanes
        t0 = time.perf_counter()
        lowered = jax.jit(sched.engine.step).lower(
            sched.arrays, sched.kv, jnp.zeros((lanes, 1), jnp.int32),
            jnp.ones((lanes,), bool))
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        rids = [sched.submit(p) for p in prompts]
        t3 = time.perf_counter()
        results = sched.run()
        r_s = time.perf_counter() - t3
        tokens[impl] = np.stack([results[r] for r in rids])
        kernel = has_kernel(compiled)
        phase_line(f"serve[{impl}]", (t1 - t0, t2 - t1, r_s),
                   requests=len(rids),
                   steps=sched.steps, tokens=tokens[impl].tolist(),
                   tpu_custom_call=kernel)
        if on_tpu:
            check(kernel == (impl == "pallas"),
                  f"serve[{impl}] tpu_custom_call={kernel}")
    same = bool(np.array_equal(tokens["chunked"], tokens["pallas"]))
    phase_line("serve_compare", (0.0, 0.0, 0.0), same_tokens=same)
    check(same, "served tokens differ between chunked and pallas")


# ---------------------------------------------------------------------------
# four chips: one client per device under shard_map
# ---------------------------------------------------------------------------

def phase_four_chips(size):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.comm import shard_map
    from repro.core.federated import ROUND_METRIC_KEYS
    from repro.kernels import ops
    from repro.models.mlp import mlp_loss
    from repro.train import federated_fit, sharded_client_fit

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    zspecs, cfg, state, batches = train_setup(**size)
    key = jax.random.PRNGKey(7)
    mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
    state_specs = jax.tree.map(lambda _: P(), state)
    met_specs = {k: P() for k in ROUND_METRIC_KEYS}
    # per-shard slab: (K, R, E, B, ...) with K the sharded mesh axis
    per_client = jax.tree.map(lambda x: np.swapaxes(np.asarray(x), 0, 1),
                              batches)

    def body(s, b, k):
        b = jax.tree.map(lambda x: x[0], b)
        return sharded_client_fit(zspecs, s, mlp_loss, b, k, cfg)

    ops.set_default_impl("pallas")
    try:
        with jax.set_mesh(mesh):
            fn = shard_map(body, ("data",), (state_specs, P("data"), P()),
                           (state_specs, met_specs))
            compiled, (sharded, mets), times = compile_and_run(
                fn, state, per_client, key)
        losses = np.asarray(mets["loss"])
        phase_line("sharded_fit", times, devices=4, rounds=losses.size,
                   loss=[round(float(x), 4) for x in losses],
                   tpu_custom_call=has_kernel(compiled))
        compiled, (single, mets), times = compile_and_run(
            lambda s, b, k: federated_fit(zspecs, s, mlp_loss, b, k, cfg),
            state, batches, key)
    finally:
        ops.set_default_impl("ref")
    losses = np.asarray(mets["loss"])
    phase_line("vmap_fit", times, devices=1, rounds=losses.size,
               loss=[round(float(x), 4) for x in losses],
               tpu_custom_call=has_kernel(compiled))
    equal = all(np.array_equal(np.asarray(sharded["scores"][p]),
                               np.asarray(single["scores"][p]))
                for p in zspecs.specs)
    dd = max_diff(sharded["dense"], single["dense"])
    phase_line("four_chip_compare", (0.0, 0.0, 0.0),
               scores_bitwise_equal=equal,
               max_abs_ddense=dd)
    check(equal, "sharded scores differ from the K=4 federated_fit")
    check(np.isfinite(losses).all(), "loss not finite")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fit on a (4,) mesh")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    from repro.models.mlp import MNISTFC_DIMS, SMALL_DIMS

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    # off the chip: the same phases at a tiny size, as a rehearsal
    size = dict(dims=MNISTFC_DIMS if on_tpu else SMALL_DIMS,
                clients=10, local_steps=10 if on_tpu else 2, rounds=3,
                batch=64 if on_tpu else 8)
    if args.four_chips:
        phase_four_chips(dict(size, clients=4))
    else:
        phase_train_compare(*phase_train(size))
        phase_serve(*((4, 4) if on_tpu else (2, 2)))
    if not on_tpu:
        print(f"no TPU (backend {dev.platform}): rehearsal only",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
