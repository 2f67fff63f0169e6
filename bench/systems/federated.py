"""Driver of the federated training cells.

The timed entry is one jitted ``federated_fit`` call over a block of
R rounds (``placement: vmap``, K clients stacked on one chip), or one
jitted ``sharded_client_fit`` inside ``shard_map`` with one client per
chip (``placement: shard_map``).  Each call is fed a fresh batch slab
made on the device from the seed (``bench.lib.data``) just before it.

Set-up builds the state and the compiled call, then drives that same
object through its first three calls; the window carries on from
there.  After the window, ``check`` replays those three calls in the
configuration's plain reference and compares losses and parameter
changes (see ``compare``).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

CHECK_STEPS = 3


def compare(prog, ref, detail=False):
    """The numbers that decide ``correct``, each a relative gap.

    ``prog`` / ``ref``: dicts with ``losses`` (every round of the
    checked calls), ``states`` [state0, state after call 1, state after
    the last checked call], each state {"words": .., "dense": ..}.

    - ``loss``: the largest |L - L_ref| / |L_ref| over the rounds;
    - ``step1_msd``: the mean squared difference between the program's
      and the reference's new broadcast (decoded probabilities) after
      the first call, over every zampled coordinate;
    - ``step1_change`` / ``step3_change``: per leaf, the gap between the
      norms of the change of that leaf (the server's pseudo-gradient
      after one call, and the change after the last), over the larger
      of the reference's norm for that leaf and the median leaf norm;
      the worst leaf.  Leaves whose reference change is under a
      thousandth of the median leaf's are left out.
    """
    def decode(kind, path, v):
        v = np.asarray(v)
        if kind == "words":  # u8 word -> its 24-bit draw threshold
            a = v.astype(np.uint64) << 16
            return (a + a // 255).astype(np.float64) / float(1 << 24)
        return v.astype(np.float64)

    def change_norms(states, idx):
        return {(kind, p): float(np.linalg.norm(
                    decode(kind, p, states[idx][kind][p])
                    - decode(kind, p, states[0][kind][p])))
                for kind in ("words", "dense") for p in states[0][kind]}

    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    gaps = np.abs(lp - lr) / np.abs(lr)
    out = {"loss": float(np.max(gaps))}
    if detail:
        out["loss_by_round"] = gaps.tolist()
    # the first call's new broadcast: mean squared difference of the
    # decoded probabilities over every zampled coordinate
    sq = [(decode("words", p, prog["states"][1]["words"][p])
           - decode("words", p, ref["states"][1]["words"][p])) ** 2
          for p in ref["states"][1]["words"]]
    out["step1_msd"] = float(np.concatenate(sq).mean())
    for name, idx in (("step1_change", 1), ("step3_change", 2)):
        n_p = change_norms(prog["states"], idx)
        n_r = change_norms(ref["states"], idx)
        med = float(np.median(list(n_r.values())))
        worst = 0.0
        for k, r in n_r.items():
            if r >= 1e-3 * med:
                worst = max(worst, abs(n_p[k] - r) / max(r, med))
        out[name] = worst
    return out


class Run:
    """One federated cell: set-up in the constructor, then ``window``
    and ``check``."""

    def __init__(self, h, cfg: Dict[str, Any], traffic: Dict[str, Any],
                 cell: Dict[str, Any], seed: int, devices):
        import jax
        import jax.numpy as jnp

        from repro.core import FederatedConfig, ZamplingConfig, build_specs
        from repro.kernels import ops
        from repro.models.mlp import init_mlp_params, mlp_loss
        from repro.train import federated_fit, sharded_client_fit

        from bench.lib import data

        self.h, self.cfg, self.traffic, self.cell = h, cfg, traffic, cell
        self.jax, self.jnp = jax, jnp
        dims = tuple(cfg["dims"])
        self.K = traffic["clients"]
        self.E = traffic["local_steps"]
        self.B = traffic["batch"]
        self.R = cell["rounds_per_call"]
        self.sharded = traffic["placement"] == "shard_map"
        z = cfg["zampling"]
        template = jax.eval_shape(lambda k: init_mlp_params(k, dims),
                                  jax.random.PRNGKey(0))
        self.zspecs = build_specs(template, ZamplingConfig(
            compression=z["compression"], d=z["d"], window=z["window"],
            seed=z["seed"], min_size=z["min_size"]))
        f = cfg["federated"]
        self.fcfg = FederatedConfig(
            num_clients=self.K, local_steps=self.E, local_lr=f["local_lr"],
            aggregate=f["aggregate"], downlink=f["downlink"])
        ops.set_default_impl(cfg["impl"])
        self.layout = [{"m": s.m, "n": s.n, "d": s.d}
                       for s in self.zspecs.specs.values()]
        self.n_params = sum(int(math.prod(l.shape))
                            for l in jax.tree.leaves(template))

        base = data.base_key(seed)
        self.keys = data.stream_keys(base)
        self.state0 = data.initial_state(self.zspecs, template,
                                         self.keys["weights"])
        if self.sharded:
            mesh = jax.make_mesh((self.K,), ("data",),
                                 devices=devices[:self.K])
            self.gen = data.batch_fn(self.keys["data"], dims[0], dims[-1],
                                     (self.K, self.R, self.E, self.B),
                                     mesh=mesh)
        else:
            self.gen = data.batch_fn(self.keys["data"], dims[0], dims[-1],
                                     (self.R, self.K, self.E, self.B))
        zspecs, fcfg = self.zspecs, self.fcfg

        if self.sharded:
            from jax.sharding import PartitionSpec as P

            from repro.comm import shard_map
            from repro.core.federated import ROUND_METRIC_KEYS

            state_specs = jax.tree.map(lambda _: P(), self.state0)
            met_specs = {k: P() for k in ROUND_METRIC_KEYS}

            def body(s, b, k):
                b = jax.tree.map(lambda x: x[0], b)
                return sharded_client_fit(zspecs, s, mlp_loss, b, k, fcfg)

            with jax.set_mesh(mesh):
                fn = shard_map(body, ("data",),
                               (state_specs, P("data"), P()),
                               (state_specs, met_specs))
        else:
            def fn(s, b, k):
                return federated_fit(zspecs, s, mlp_loss, b, k, fcfg)

        b0 = self.gen(0)
        with h.span("bench.compile"), \
                jax.default_matmul_precision(cfg["matmul_precision"]):
            if self.sharded:
                with jax.set_mesh(mesh):
                    self.fit = jax.jit(fn).lower(
                        self.state0, b0, self.call_key(0)).compile()
            else:
                self.fit = jax.jit(fn).lower(
                    self.state0, b0, self.call_key(0)).compile()

        # the first calls, through the window's own object and feed
        state, self.losses, self.states = self.state0, [], [self.host(
            self.state0)]
        for c in range(CHECK_STEPS):
            state, mets = self.fit(state, self.gen(c), self.call_key(c))
            self.losses.extend(np.asarray(mets["loss"]).tolist())
            if c in (0, CHECK_STEPS - 1):
                self.states.append(self.host(state))
        self.state = state
        self.calls = CHECK_STEPS
        jax.block_until_ready(self.state)

    # ---------------------------------------------------------------------
    def call_key(self, c: int):
        return self.jax.random.fold_in(self.keys["calls"], c)

    def host(self, state):
        return {"words": {p: np.asarray(v) for p, v in state["scores"].items()},
                "dense": {p: np.asarray(v) for p, v in state["dense"].items()}}

    def work(self) -> Dict[str, Any]:
        """Shapes and counts the per-layer readers need."""
        return {
            "layout": self.layout,
            "n_params": self.n_params,
            "clients": self.K,
            "clients_per_chip": 1 if self.sharded else self.K,
            "local_steps": self.E,
            "batch": self.B,
        }

    def window(self, seconds: float) -> Dict[str, Any]:
        """Run calls until ``seconds`` have passed; every call is waited
        for, so the window ends when its last call is done."""
        jax, h = self.jax, self.h
        state, calls, failed = self.state, 0, 0
        t0 = h.now()
        while True:
            c = self.calls + calls
            with h.span("bench.feed"):
                batches = self.gen(c)
            with h.span("bench.fit"):
                state, mets = self.fit(state, batches, self.call_key(c))
            with h.span("bench.wait"):
                loss = np.asarray(mets["loss"])
            failed += int(np.sum(~np.isfinite(loss)))
            calls += 1
            if h.now() - t0 >= seconds:
                break
        elapsed = h.now() - t0
        self.state, self.calls = state, self.calls + calls
        rounds = calls * self.R
        return {"elapsed_s": elapsed, "units": rounds,
                "attempted": rounds, "failed": failed,
                "end_to_end": {"round_ms": elapsed * 1e3 / rounds}}

    # ---------------------------------------------------------------------
    def free(self):
        del self.fit, self.state
        import gc
        gc.collect()

    def reference_run(self, precision: str):
        """The first calls replayed by the configuration's plain
        reference at ``precision``."""
        jax, jnp = self.jax, self.jnp
        t0 = self.h.now()
        ref = self.h.load_reference(self.cfg)
        traffic = dict(self.traffic, clients=self.K)
        round_fn, _, _, qtables = ref.make_round(self.cfg, traffic,
                                                 precision)
        round_j = jax.jit(round_fn)
        qs = jax.jit(qtables)()
        words = {p: jnp.asarray(v) for p, v in self.states[0]["words"].items()}
        dense = {p: jnp.asarray(v) for p, v in self.states[0]["dense"].items()}
        losses, states = [], [self.states[0]]
        for c in range(CHECK_STEPS):
            b = self.gen(c)
            x, y = np.asarray(b["x"]), np.asarray(b["y"])
            if self.sharded:  # (K, R, ...) -> (R, K, ...)
                x, y = np.swapaxes(x, 0, 1), np.swapaxes(y, 0, 1)
            keys = jax.random.split(self.call_key(c), self.R)
            for r in range(self.R):
                words, dense, loss = round_j(qs, words, dense,
                                             jnp.asarray(x[r]),
                                             jnp.asarray(y[r]), keys[r], r)
                losses.append(float(loss))
            if c in (0, CHECK_STEPS - 1):
                states.append(self.host({"scores": words, "dense": dense}))
        self.reference_s = self.h.now() - t0
        return {"losses": losses, "states": states}

    def readings(self, control: bool = False, detail: bool = False):
        """The compared numbers of the program against the reference;
        with ``control``, also those of the reference at the lower
        precision put in the program's place."""
        ref = self.reference_run("highest")
        out = {"program": compare(
            {"losses": self.losses, "states": self.states}, ref, detail)}
        if detail:
            out["reference_s"] = self.reference_s
        if control:
            out["control"] = compare(self.reference_run("high"), ref,
                                     detail)
        return out

    def check(self):
        """[(name, value, limit)] once the window has closed."""
        self.free()
        limits = self.cell["limits"]
        got = self.readings()["program"]
        return [(k, got[k], limits[k]) for k in limits]
