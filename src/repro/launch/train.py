"""End-to-end LM training driver.

Trains an assigned architecture (optionally size-scaled) with FEDERATED
ZAMPLING on the synthetic Markov LM stream, on whatever devices exist
(1 CPU in this container; the production mesh via --mesh pod on real
hardware).  Demonstrates the full system: config -> model -> zampling
reparam -> federated rounds -> checkpoint.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --scale 0.25 --rounds 30 --local-steps 2 --clients 4 \
      --compression 8 --out runs/demo
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import save_checkpoint
from ..configs.registry import get_arch
from ..core import (
    FederatedConfig,
    ZamplingConfig,
    build_specs,
    federated_round,
    init_state,
)
from ..data import lm_token_batches
from ..models.model import build_model, loss_fn


def scaled(cfg, scale: float):
    """Shrink width/depth by ~scale (keeps the family & flavour)."""
    if scale >= 1.0:
        return cfg
    d = int(cfg.d_model * scale**0.5) // 64 * 64 or 64
    L = max(2, int(cfg.n_layers * scale**0.5))
    heads = max(1, int(cfg.n_heads * scale**0.5)) if cfg.n_heads else 0
    kv = max(1, min(cfg.n_kv, heads)) if cfg.n_kv else 0
    if heads:
        while heads % kv:
            kv -= 1
    return dataclasses.replace(
        cfg, d_model=d, n_layers=L, n_heads=heads, n_kv=kv,
        head_dim=64 if heads else 0,
        d_ff=int(cfg.d_ff * scale**0.5) // 64 * 64 if cfg.d_ff else 0,
        vocab=min(cfg.vocab, 8192), dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--compression", type=float, default=8.0)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--out", default="runs/demo")
    args = ap.parse_args()

    cfg = scaled(get_arch(args.arch), args.scale)
    model = build_model(cfg)
    params_t = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_t))
    zspecs = build_specs(
        params_t,
        ZamplingConfig(compression=args.compression, d=args.d,
                       min_size=4096),
    )
    print(f"[train] arch={cfg.name} scaled: {n_params/1e6:.1f}M params, "
          f"reparam {zspecs.m_total/1e6:.1f}M -> {zspecs.n_total/1e6:.2f}M "
          f"trainable ({zspecs.compression:.1f}x), client upload/round = "
          f"{zspecs.n_total/8/1e3:.0f} KB vs naive "
          f"{zspecs.m_total*4/1e6:.0f} MB")

    # dense leaves initialised from a real model init
    real = model.init_params(jax.random.PRNGKey(0))
    state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=real)
    del real

    fcfg = FederatedConfig(num_clients=args.clients,
                           local_steps=args.local_steps, local_lr=args.lr)

    def mloss(params, batch):
        return loss_fn(model, params, batch)

    @jax.jit
    def round_fn(state, batch, key):
        return federated_round(zspecs, state, mloss, batch, key, fcfg)

    stream = lm_token_batches(cfg.vocab, args.clients * args.local_steps
                              * args.batch, args.seq + 1, seed=0)
    key = jax.random.PRNGKey(0)
    os.makedirs(args.out, exist_ok=True)
    history = []
    for r in range(args.rounds):
        toks = next(stream).reshape(args.clients, args.local_steps,
                                    args.batch, args.seq + 1)
        batch = {"tokens": jnp.asarray(toks[..., :-1]),
                 "labels": jnp.asarray(toks[..., :-1])}
        key, sub = jax.random.split(key)
        t0 = time.time()
        state, met = round_fn(state, batch, sub)
        loss = float(met["loss"])  # waits for the round
        dt = time.time() - t0
        history.append(loss)
        print(f"[round {r:3d}] loss={loss:.4f}  ({dt:.1f}s)", flush=True)

    save_checkpoint(os.path.join(args.out, "ckpt"), state,
                    meta={"arch": cfg.name, "q_seed": 0,
                          "rounds": args.rounds,
                          "compression": zspecs.compression})
    with open(os.path.join(args.out, "history.json"), "w") as f:
        json.dump(history, f)
    print(f"[train] done. loss {history[0]:.3f} -> {history[-1]:.3f}; "
          f"checkpoint at {args.out}/ckpt.npz")


if __name__ == "__main__":
    main()
