"""Record a small TPU trace of a program with two named scopes and one
Pallas call, for the tests of the scope attribution (``bench.lib.scopes``).

  python scripts/record_scoped_trace.py tests/data/scoped.xplane.pb

Runs on one TPU.  The program is a gradient step: a Pallas kernel
behind a ``custom_vjp`` (its forward compiles to ``%jvp__.N``, the name
the ``reconstruct_roofline`` reader matches), the loss under
``fed.model``, the update under ``fed.update`` and a sort under no
scope.  Three calls, each in a ``bench.fit`` host span, run inside a
``bench.window`` span, after a warm-up call.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SHAPE = (256, 256)


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.custom_vjp
def double(x):
    return pl.pallas_call(
        _double_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def _double_fwd(x):
    return double(x), None


def _double_bwd(_, g):
    return (2.0 * g,)


double.defvjp(_double_fwd, _double_bwd)


def loss(x, y):
    w = double(x)
    with jax.named_scope("fed.model"):
        return jnp.sum(jnp.tanh(w @ y))


@jax.jit
def step(x, y):
    value, grad = jax.value_and_grad(loss)(x, y)
    with jax.named_scope("fed.update"):
        x = x - 0.01 * (grad @ y)  # a contraction XLA does not fuse away
    return x, value, jnp.sort(x, axis=-1)  # an op under no scope


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, SHAPE, jnp.float32)
    y = jax.random.normal(ky, SHAPE, jnp.float32)
    print(step.lower(x, y).compile().as_text().count("tpu_custom_call"),
          "tpu_custom_call in the compiled step")
    x, v, _ = step(x, y)
    jax.block_until_ready(x)
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.fit"):
                    x, v, _ = step(x, y)
                    jax.block_until_ready(x)
        jax.profiler.stop_trace()
        (trace,) = Path(tmp).glob("**/*.xplane.pb")
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out} ({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
