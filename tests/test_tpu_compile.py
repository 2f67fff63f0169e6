"""The main-path Pallas kernels compile for a TPU v5e (no chip needed).

Each test compiles one kernel with ``interpret=False`` against a
described (not attached) v5e chip, at the paper's MNIST-FC
``layer0/kernel`` widths (784x300, m/n = 8, d = 10, window 128, K = 10
clients: n = 29,440, 1,023 rows per window) or the reduced qwen2-0.5b
serve widths, and asserts the kernel is in the compiled program
(``tpu_custom_call``).  What the interpreter accepts and the TPU
compiler refuses — unsupported casts, lane-crossing reshapes,
misaligned blocks — fails here.

The topology is described only inside the module fixture, never at
import or collection: one process at a time may load the TPU compiler
library, so under parallel test workers only the worker that runs this
file loads it, and every worker still collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qspec import make_qspec
from repro.kernels import qz_decode, qz_reconstruct

K = 10


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def layer0():
    return make_qspec(1, (784, 300), 784, compression=8, d=10, window=128)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_layer0_widths(layer0):
    assert (layer0.n, layer0.rows_per_window) == (29_440, 1_023)


def test_reconstruct_batched_fwd(chip, layer0):
    _compile(chip, lambda Z: qz_reconstruct.qz_reconstruct_batched_fwd(
        layer0, Z, interpret=False), ((K, layer0.n), jnp.float32))


def test_reconstruct_batched_bwd_plan(chip, layer0):
    _compile(chip, lambda G: qz_reconstruct.qz_reconstruct_batched_bwd_plan(
        layer0, G, interpret=False), ((K, layer0.m), jnp.float32))


@pytest.mark.parametrize("qbits,dtype", [(None, jnp.float32),
                                         (8, jnp.uint8)])
def test_sample_reconstruct_batched_fwd(chip, layer0, qbits, dtype):
    _compile(chip, lambda P, s: qz_reconstruct.qz_sample_reconstruct_batched_fwd(
        layer0, P, s, qbits=qbits, interpret=False),
        ((K, layer0.n), dtype), ((K,), jnp.uint32))


def test_sample_pack_batched_fwd(chip, layer0):
    _compile(chip, lambda P, s: qz_reconstruct.qz_sample_pack_batched_fwd(
        layer0, P, s, interpret=False),
        ((K, layer0.n), jnp.float32), ((K,), jnp.uint32))


def test_sample_matmul(chip):
    # the reduced qwen2-0.5b MLP up-projection (d_model 256 -> d_ff 512)
    spec = make_qspec(2, (256, 512), 256, compression=4, d=12, window=128)
    _compile(chip, lambda p, s, X: qz_decode.qz_sample_matmul(
        spec, p, s, X, d_in=256, d_out=512, qbits=8, interpret=False),
        ((spec.n,), jnp.uint8), ((), jnp.uint32), ((4, 256), jnp.float32))
