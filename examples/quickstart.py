"""Quickstart: Zampling in 60 lines.

Reparametrize a small MLP with w = Q z (m/n = 4, d = 5), train the
probability vector by sampling (LOCAL ZAMPLING, paper §1.3), and show
that sampled networks match the expected network's accuracy.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import ZamplingConfig, build_specs, init_state
from repro.data import make_teacher_dataset
from repro.models.mlp import SMALL_DIMS, init_mlp_params, mlp_accuracy, mlp_loss
from repro.train import LocalTrainConfig, evaluate, train_local_zampling

enable_compile_cache()

ds = make_teacher_dataset(n_train=6000, n_test=1200, seed=0)
test_batch = {"x": jnp.asarray(ds.x_test), "y": jnp.asarray(ds.y_test)}

# 1. template network -> QSpecs (the influence matrix, never materialized)
template = init_mlp_params(jax.random.PRNGKey(0), SMALL_DIMS)
zspecs = build_specs(
    template, ZamplingConfig(compression=4.0, d=5, window=128, min_size=128)
)
print(f"weights m={zspecs.m_total}, trainable n={zspecs.n_total} "
      f"({zspecs.compression:.1f}x compression)")
bits = zspecs.comm_bits_per_round()
print(f"federated client upload: {bits['client_up']} bits vs naive "
      f"{bits['naive_client_up']} ({bits['naive_client_up']/bits['client_up']:.0f}x)")

# 2. train-by-sampling: fresh Bernoulli mask every forward pass
state = init_state(jax.random.PRNGKey(1), zspecs, dense_init=template)
batches = ({"x": jnp.asarray(x), "y": jnp.asarray(y)}
           for x, y in ds.batches(128, seed=0))
state, hist = train_local_zampling(
    zspecs, state, mlp_loss, batches,
    LocalTrainConfig(steps=800, lr=1e-2),
)
print(f"loss: {hist['loss'][0]:.3f} -> {hist['loss'][-1]:.3f}")

# 3. evaluate sampled vs expected networks
acc = jax.jit(lambda p: mlp_accuracy(p, test_batch))
mean_s, std_s = evaluate(zspecs, state, acc, jax.random.PRNGKey(2),
                         n_samples=20)
mean_e, _ = evaluate(zspecs, state, acc, jax.random.PRNGKey(2),
                     mode="continuous")
print(f"sampled accuracy  {mean_s:.3f} +- {std_s:.3f}")
print(f"expected accuracy {mean_e:.3f}  (paper: the two should be close "
      f"after training-by-sampling)")
