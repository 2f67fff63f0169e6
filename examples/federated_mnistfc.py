"""FEDERATED ZAMPLING end-to-end (paper §3.2 setup, CPU scale).

10 clients, MNISTFC-family network, m/n = 8: each round the clients
upload n BITS (the sampled masks) instead of 32m float bits — a 256x
reduction — and the server averages masks into the new probability
vector.  ``--aggregate`` picks the wire transport (mean_f32 baseline,
psum_u32 popcount psum, allgather_packed raw lanes; all bit-exact
against each other — only the measured bytes differ).  ``--downlink``
picks the server broadcast codec (f32 oracle, u16/u8 quantized
probability words — 2x/4x less downlink; the carried state between
rounds IS the encoded wire representation, and eval samples networks
straight from it).

Rounds run through the ``federated_fit`` scan driver: the loop below
compiles ONE (block, K, E)-shaped program and re-dispatches it per
eval block, instead of one dispatch (and, across (K, E) changes, one
compile) per round.

Partial participation (``repro.fault``): ``--population N`` switches
to a Dirichlet-split population of N virtual clients of UNEQUAL size,
of which ``--cohort K`` are sampled each round by the deterministic
counter-hash cohort draw; ``--dropout-rate p`` makes each sampled
client drop the round with probability p (drawn reproducibly per
(round, client)).  The server then computes the sample-count-weighted
mean over the realized survivors and the run prints a per-round
participation/fault table with the REALIZED wire bytes.

  PYTHONPATH=src python examples/federated_mnistfc.py [--rounds 25]
  PYTHONPATH=src python examples/federated_mnistfc.py \
      --population 100 --cohort 10 --dropout-rate 0.2
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.metering import downlink_table, round_wire_report, wire_table
from repro.core import (
    FederatedConfig, ZamplingConfig, build_specs, encode_state, init_state,
)
from repro.data import (
    client_batch_stream,
    cohort_batch_stream,
    dirichlet_client_split,
    iid_client_split,
    make_teacher_dataset,
)
from repro.fault import ClientPopulation, FaultPlan
from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_accuracy, mlp_loss
from repro.train import evaluate, federated_fit
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--rounds", type=int, default=25)
ap.add_argument("--clients", type=int, default=10)
ap.add_argument("--local-steps", type=int, default=30)
ap.add_argument("--compression", type=float, default=8.0)
ap.add_argument("--aggregate", default="psum_u32",
                help="wire transport: mean_f32 | psum_u32 | allgather_packed")
ap.add_argument("--downlink", default="u8",
                help="server broadcast codec: f32 | u16 | u8 | "
                     "packed4 | packed2 (sub-byte words in uint32 lanes)")
ap.add_argument("--downlink-schedule", default="constant",
                help="downlink rate schedule: constant | cosine (anneal "
                     "width up over --rounds) | frontier (per-tensor "
                     "width from the measured draw-word flip fraction); "
                     "the realized per-round bytes are metered in the "
                     "'down' column")
ap.add_argument("--schedule-b-min", type=int, default=2,
                help="minimum scheduled width in bits (cosine start / "
                     "frontier floor)")
ap.add_argument("--block", type=int, default=5,
                help="rounds per compiled scan block (and eval period)")
ap.add_argument("--population", type=int, default=0,
                help="total virtual clients N (0 = every client "
                     "participates every round, the paper's setup)")
ap.add_argument("--cohort", type=int, default=0,
                help="clients sampled per round (default: --clients)")
ap.add_argument("--dropout-rate", type=float, default=0.0,
                help="per-round probability a sampled client drops")
ap.add_argument("--beta", type=float, default=0.5,
                help="Dirichlet concentration of the non-IID split")
ap.add_argument("--min-clients", type=int, default=1,
                help="skip rounds with fewer survivors than this")
ap.add_argument("--stream-chunk", type=int, default=0,
                help="fold uploads this many clients at a time (streaming "
                     "cohort accumulator; 0 = one-shot slab aggregation; "
                     "scores are bit-identical either way)")
ap.add_argument("--het-table", action="store_true",
                help="print the heterogeneity table (accuracy vs Dirichlet "
                     "beta per downlink codec) and exit")
args = ap.parse_args()

if args.het_table:
    from repro.experiments import run_heterogeneity

    print("accuracy vs Dirichlet beta x downlink codec (quick grid)")
    print(f"{'beta':>6} {'codec':>6} {'acc':>7} {'std':>6} "
          f"{'down KiB':>9} {'vs f32':>7}")
    for row in run_heterogeneity(quick=True):
        print(f"{row['beta']:>6.2f} {row['codec']:>6} "
              f"{row['final_sampled_acc']:>7.3f} {row['sampled_std']:>6.3f} "
              f"{row['downlink_bytes_per_client'] / 1024:>9.1f} "
              f"{row['downlink_vs_f32']:>7.4f}")
    raise SystemExit(0)

use_cohort = args.population > 0
cohort = args.cohort or args.clients
if use_cohort and cohort > args.population:
    ap.error(f"--cohort {cohort} exceeds --population {args.population}")

ds = make_teacher_dataset(n_train=8000, n_test=1500, seed=0)
template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
zspecs = build_specs(template, ZamplingConfig(
    compression=args.compression, d=10, window=128, min_size=128))
state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)

rep = round_wire_report(zspecs, args.aggregate,
                        cohort if use_cohort else args.clients,
                        downlink=args.downlink)
print(f"m={zspecs.m_total} n={zspecs.n_total}; transport={rep['transport']}: "
      f"client upload {rep['uplink_bytes_per_client']/1024:.1f} KiB/round vs "
      f"naive f32 {rep['naive_uplink_bytes_per_client']/1024:.1f} KiB "
      f"({rep['naive_uplink_bytes_per_client']/rep['uplink_bytes_per_client']:.0f}x less)")
for row in wire_table(zspecs, args.clients, downlink=args.downlink):
    print(f"  {row['strategy']:>17}: {row['uplink_bytes_per_client']/1024:8.1f}"
          f" KiB/client/round ({row['uplink_vs_f32']:.4f}x of f32)")
print(f"downlink codec={rep['downlink']}: server broadcast "
      f"{rep['downlink_bytes_per_client']/1024:.1f} KiB/client/round "
      f"({rep['downlink_vs_f32']:.4f}x of f32)")
for row in downlink_table(zspecs, args.clients, aggregate=args.aggregate):
    print(f"  {row['codec']:>17}: {row['downlink_bytes_per_client']/1024:8.1f}"
          f" KiB/client/round ({row['downlink_vs_f32']:.4f}x of f32)")

if use_cohort:
    clients, hist = dirichlet_client_split(ds, args.population,
                                           beta=args.beta, seed=0)
    sizes = hist.sum(axis=1)
    pop = ClientPopulation(args.population,
                           sample_counts=tuple(int(s) for s in sizes),
                           seed=0)
    plan = FaultPlan(dropout=args.dropout_rate)
    stream = cohort_batch_stream(clients, pop, cohort, 64,
                                 args.local_steps, seed=0)
    print(f"population N={args.population} (Dirichlet beta={args.beta}, "
          f"client sizes {sizes.min()}..{sizes.max()}), cohort K={cohort}, "
          f"dropout p={args.dropout_rate}")
else:
    plan = None
    clients = iid_client_split(ds, args.clients)
    stream = client_batch_stream(clients, 64, args.local_steps, seed=0)
sched_kw = {}
if args.downlink_schedule != "constant":
    sched_kw = {"downlink_schedule": args.downlink_schedule,
                "schedule_b_min": args.schedule_b_min}
    if args.downlink_schedule == "cosine":
        sched_kw["schedule_rounds"] = args.rounds
fcfg = FederatedConfig(num_clients=cohort if use_cohort else args.clients,
                       local_steps=args.local_steps, local_lr=0.5,
                       aggregate=args.aggregate, downlink=args.downlink,
                       min_clients=args.min_clients,
                       stream_chunk=args.stream_chunk, **sched_kw)
# the round carry is the ENCODED broadcast: quantized codecs carry
# uint8/uint16 wire words between rounds, never an f32 score slab
state = encode_state(zspecs, fcfg, state)
acc = jax.jit(lambda p: mlp_accuracy(
    p, {"x": jnp.asarray(ds.x_test), "y": jnp.asarray(ds.y_test)}))


# ONE compile for the whole run: every block has the same
# (block, K, E, batch) shape, so this traces exactly once.
if use_cohort:
    @jax.jit
    def fit_block(state, batches, key, ids, weights):
        return federated_fit(zspecs, state, mlp_loss, batches, key, fcfg,
                             client_ids=ids, weights=weights, faults=plan)
else:
    @jax.jit
    def fit_block(state, batches, key):
        return federated_fit(zspecs, state, mlp_loss, batches, key, fcfg)


FAULT_COLS = ("num_participating", "num_dropped", "num_stragglers",
              "num_corrupt", "num_duplicates", "round_skipped")

key = jax.random.PRNGKey(0)
done = 0
total_down = 0.0
if use_cohort:
    print(f"{'round':>5} {'part':>4} {'drop':>4} {'strag':>5} {'corr':>4} "
          f"{'dup':>3} {'skip':>4} {'w_sum':>7} {'uplink KiB':>10} "
          f"{'down KiB':>8}")
while done < args.rounds:
    # a tail block smaller than --block recompiles once for its shape
    r = min(args.block, args.rounds - done)
    key, sub = jax.random.split(key)
    if use_cohort:
        ids, ws, xs, ys = zip(*(next(stream) for _ in range(r)))
        state, mets = fit_block(
            state,
            {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))},
            sub, jnp.asarray(np.stack(ids)), jnp.asarray(np.stack(ws)),
        )
        cols = {c: np.asarray(mets[c]) for c in FAULT_COLS}
        up = np.asarray(mets["uplink_bytes_round"])
        down = np.asarray(mets["downlink_bytes_per_client"])
        wsum = np.asarray(mets["weight_sum"])
        for j in range(r):
            print(f"{done + j:>5} {cols['num_participating'][j]:>4.0f} "
                  f"{cols['num_dropped'][j]:>4.0f} "
                  f"{cols['num_stragglers'][j]:>5.0f} "
                  f"{cols['num_corrupt'][j]:>4.0f} "
                  f"{cols['num_duplicates'][j]:>3.0f} "
                  f"{cols['round_skipped'][j]:>4.0f} "
                  f"{wsum[j]:>7.0f} {up[j] / 1024:>10.1f} "
                  f"{down[j] / 1024:>8.1f}")
    else:
        xs, ys = zip(*(next(stream) for _ in range(r)))
        state, mets = fit_block(
            state,
            {"x": jnp.asarray(np.stack(xs)), "y": jnp.asarray(np.stack(ys))},
            sub,
        )
    done += r
    ms, std = evaluate(zspecs, state, acc, jax.random.PRNGKey(3),
                       n_samples=10, carried=args.downlink)
    losses = np.asarray(mets["loss"])
    # realized (metered) downlink bytes per client, per round — a
    # scheduled run charges only the scheduled width + lane padding
    down = np.asarray(mets["downlink_bytes_per_client"], np.float64)
    total_down += float(down.sum())
    down_col = " ".join(f"{b / 1024:.1f}" for b in down)
    print(f"round {done:3d}: loss={losses[-1]:.3f} "
          f"(block mean {losses.mean():.3f}) "
          f"sampled-acc={ms:.3f}+-{std:.3f} down/client KiB: {down_col}")
print(f"cumulative downlink: {total_down / 1024:.1f} KiB/client over "
      f"{args.rounds} rounds ({args.downlink}, "
      f"schedule={args.downlink_schedule})")
print("done — every upload was a binary mask and every broadcast was "
      f"{args.downlink} wire words, never a naive float tensor.")
