"""Serve a generative LM from the COMPRESSED Zampling artifact.

The deployment object is the encoded score broadcast (u8/u16 wire
words or f32 scores) + dense leaves + one uint32 draw word.  Three
ways to decode against it:

  --mode load       reconstruct w = Q Bern(f(s)) once, serve resident
                    f32 tensors (the PR-5-era trade);
  --mode streaming  never materialize a weight: every decode linear
                    regenerates its (window, bm) block inside the
                    contraction (kernels.ops serve section);
  --mode cached     streaming plus the hot-block tile pool: the first
                    --cache-budget-kib of canonical tiles serve
                    resident, the rest stream — the dialable midpoint.

Bit-identical logits in all three; the resident table below meters
the FULL node (words + tile pool + lane KV + dense), not words only
(comm.metering.serve_resident_bytes).

The batched section drives the continuous-batching scheduler: ragged
prompts admitted/retired per step over fixed lanes, bitwise equal to
the single-request path.  With --delta, a synthetic converged round
(1% of scores move) ships as an XOR word delta and hot-swaps the live
scheduler MID-FLIGHT — the hot-block cache survives, dropping only
the tiles whose drawn mask bits actually flipped.

  PYTHONPATH=src python examples/serve_compressed.py \
      --mode cached --delta
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.comm.metering import serve_resident_bytes
from repro.core import ZamplingConfig, build_specs, init_state, sample_masks
from repro.serve import (
    ServeConfig,
    ServeScheduler,
    apply_delta,
    build_cache,
    build_serve_engine,
    delta_report,
    make_delta,
    make_generator,
    make_serve_state,
    serve_from_compressed,
)
from repro.models import build_model
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

parser = argparse.ArgumentParser()
parser.add_argument("--mode", choices=["load", "streaming", "cached"],
                    default="cached",
                    help="serving mode for the timed generation")
parser.add_argument("--delta", action="store_true",
                    help="also demo the XOR delta hot-swap round update")
parser.add_argument("--codec", choices=["f32", "u16", "u8"], default="u8",
                    help="downlink codec carried by the serving state")
parser.add_argument("--cache-budget-kib", type=int, default=2048,
                    help="hot-block tile pool budget (mode=cached)")
parser.add_argument("--lanes", type=int, default=4,
                    help="scheduler batch lanes")
parser.add_argument("--new-tokens", type=int, default=8)
args = parser.parse_args()

cfg = get_arch("qwen2-0.5b").reduced()
model = build_model(cfg)
params_t = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
zspecs = build_specs(params_t, ZamplingConfig(compression=8, d=8,
                                              min_size=1024))
state = init_state(jax.random.PRNGKey(1), zspecs,
                   dense_init=model.init_params(jax.random.PRNGKey(0)))

masks = sample_masks(zspecs, state, jax.random.PRNGKey(2))
mask_bits = sum(int(m.shape[0]) for m in masks.values())
print(f"compressed artifact: {mask_bits/8/1024:.1f} KiB of masks for "
      f"{zspecs.m_total/1e6:.1f}M weights "
      f"(+{sum(int(jnp.size(v)) for v in state['dense'].values())/1e3:.0f}K "
      f"dense params)")

prompt = jnp.asarray([[5, 17, 42, 7], [1, 2, 3, 4]], jnp.int32)
out = serve_from_compressed(model, zspecs, masks, state["dense"], prompt,
                            max_new_tokens=8, seq_len=32)
print("batched generation (legacy mask artifact, reconstruct-on-load):")
for row in out.tolist():
    print("  ", row)

# --- the Zampling-native serving state -----------------------------------
sstate = make_serve_state(zspecs, state, jax.random.PRNGKey(2),
                          downlink=args.codec, dither_word=0)
B, Sp = prompt.shape
seq_len = Sp + args.new_tokens
budget = args.cache_budget_kib * 1024

print(f"\nresident node state ({args.codec} codec; words + cache pool + "
      f"KV + dense) and decode throughput, mode={args.mode} timed:")
print(f"  {'mode':<11} {'zampled KiB':>12} {'cache KiB':>10} "
      f"{'KV KiB':>7} {'total KiB':>10} {'tok/s':>10}")
rows = {}
for mode in ("load", "streaming", "cached"):
    engine = build_serve_engine(model, sstate, mode=mode)
    hbc = None
    if mode == "cached":
        hbc = build_cache(sstate, ServeConfig(
            lanes=args.lanes, seq_len=seq_len,
            cache_budget_bytes=budget, mode="cached"))
    arrays = engine.arrays_of(sstate, cache=hbc)
    run = make_generator(engine.step, args.new_tokens)
    cache = engine.init_cache(B, seq_len)
    toks, _ = run(arrays, cache, prompt, jax.random.PRNGKey(0))
    toks.block_until_ready()  # compile + correctness reference
    rows[mode] = toks
    res = serve_resident_bytes(sstate, budget if mode == "cached" else 0,
                               mode=mode, kv_cache=cache)
    if mode == args.mode:
        t0 = time.perf_counter()
        out2, _ = run(arrays, cache, prompt, jax.random.PRNGKey(0))
        out2.block_until_ready()
        dt = time.perf_counter() - t0
        tps = f"{B * args.new_tokens / dt:10.1f}"
    else:
        tps = f"{'-':>10}"
    print(f"  {mode:<11} {res['zampled_bytes']/1024:12.1f} "
          f"{res['cache_bytes']/1024:10.1f} {res['kv_bytes']/1024:7.1f} "
          f"{res['total_bytes']/1024:10.1f} {tps}")
assert (rows["load"] == rows["streaming"]).all(), "modes must agree bitwise"
assert (rows["load"] == rows["cached"]).all(), "cached mode must agree too"
print("  (modes verified bit-identical; dense leaves "
      f"{sstate.dense_bytes()/1024:.1f} KiB in all modes)")

# --- continuous batching --------------------------------------------------
print(f"\ncontinuous batching: {args.lanes} lanes, ragged prompts, "
      f"mode={args.mode}:")
ragged = [[5, 17, 42, 7], [1, 2, 3], [9, 9, 1, 0, 3], [4, 4]]
scfg = ServeConfig(lanes=args.lanes,
                   seq_len=max(len(p) for p in ragged) + args.new_tokens,
                   cache_budget_bytes=budget, mode=args.mode,
                   max_new_tokens=args.new_tokens)
sched = ServeScheduler(model, sstate, scfg)
rids = {sched.submit(p): p for p in ragged}
t0 = time.perf_counter()
results = sched.run()
dt = time.perf_counter() - t0
for rid, p in rids.items():
    print("  ", p, "->", results[rid].tolist())
m = sched.metrics()
print(f"  {m['completed']} requests in {m['steps']} engine steps "
      f"({sum(len(v) for v in results.values())/dt:.1f} tok/s incl. "
      "compile)")
if "cache" in m:
    c = m["cache"]
    print(f"  cache: {c['resident_tiles']}/{c['total_tiles']} tiles "
          f"resident, {c['hits']} hits / {c['misses']} misses")

if args.delta:
    print("\ndelta hot-swap (synthetic converged round: 1% of scores move):")
    key = jax.random.PRNGKey(7)
    scores2 = {}
    for p, s in state["scores"].items():
        k1, k2, key = jax.random.split(key, 3)
        touch = jax.random.bernoulli(k1, 0.01, s.shape)
        scores2[p] = jnp.where(
            touch, s + 0.05 * jax.random.normal(k2, s.shape), s)
    state2 = {"scores": scores2, "dense": state["dense"]}
    print(f"  {'codec':<6} {'changed':>8} {'flipped':>8} {'delta KiB':>10} "
          f"{'full KiB':>9} {'ratio':>7}")
    for codec in ("f32", "u16", "u8"):
        s1 = make_serve_state(zspecs, state, jax.random.PRNGKey(2),
                              downlink=codec, dither_word=0)
        s2 = make_serve_state(zspecs, state2, jax.random.PRNGKey(2),
                              downlink=codec, dither_word=0)
        rep = delta_report(s1, s2)
        print(f"  {codec:<6} {rep['words_changed']:>8} "
              f"{rep['words_flipped']:>8} "
              f"{rep['delta_bytes']/1024:10.1f} "
              f"{rep['full_bytes']/1024:9.1f} "
              f"{rep['delta_vs_full']:7.4f}")
    delta = make_delta(sstate, make_serve_state(
        zspecs, state2, jax.random.PRNGKey(2), downlink=args.codec,
        dither_word=0))
    # swap the LIVE scheduler mid-queue: in-flight KV survives, and in
    # cached mode only flipped-bit tiles drop from the pool
    for p in ragged:
        sched.submit(p)
    sched.step_once()
    before = (sched.cache.resident_tiles if sched.cache else None)
    sched.apply_round_delta(delta)
    results2 = sched.run()
    if sched.cache is not None:
        c = sched.cache.stats()
        print(f"  cache survived swap: {c['invalidations']} tiles "
              f"invalidated of {before}, refilled to "
              f"{c['resident_tiles']}/{c['total_tiles']}")
    print("  post-swap generations (same compiled step, new words):")
    for rid in sorted(results2)[len(rids):]:
        print("  ", results2[rid].tolist())
