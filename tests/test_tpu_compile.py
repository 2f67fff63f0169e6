"""The main-path Pallas kernels compile for a TPU v5e (no chip needed).

Each test compiles one kernel with ``interpret=False`` against a
described (not attached) v5e chip, at the paper's MNIST-FC
``layer0/kernel`` widths (784x300, m/n = 8, d = 10, window 128, K = 10
clients: n = 29,440, 1,023 rows per window) or the reduced qwen2-0.5b
serve widths, and asserts the kernel is in the compiled program
(``tpu_custom_call``).  What the interpreter accepts and the TPU
compiler refuses — unsupported casts, lane-crossing reshapes,
misaligned blocks — fails here.

``test_round_trace_names`` compiles a whole federated round the same
way and guards the names the benchmark's trace readers match: the two
kernels' HLO instruction names (``bench/metrics/reconstruct_roofline.py``
and ``bwd_plan_roofline.py``), which no named scope may rename, and the
program's scopes on the ops around them.

The topology is described only inside the module fixture, never at
import or collection: one process at a time may load the TPU compiler
library, so under parallel test workers only the worker that runs this
file loads it, and every worker still collects the same tests.
"""

import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qspec import make_qspec
from repro.core.transpose_plan import build_block_plan
from repro.kernels import qz_decode, qz_reconstruct

K = 10
ROOT = Path(__file__).resolve().parents[1]
OP_NAME = re.compile(r'op_name="([^"]*)"')


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
    return text


def test_layer0_widths(layer0):
    assert (layer0.n, layer0.rows_per_window) == (29_440, 1_023)


def test_reconstruct_batched_fwd(chip, layer0):
    _compile(chip, lambda Z: qz_reconstruct.qz_reconstruct_batched_fwd(
        layer0, Z, interpret=False), ((K, layer0.n), jnp.float32))


def test_reconstruct_batched_bwd_plan(chip, layer0):
    _compile(chip, lambda G: qz_reconstruct.qz_reconstruct_batched_bwd_plan(
        layer0, G, interpret=False), ((K, layer0.m), jnp.float32))


def test_reconstruct_batched_bwd_plan_cell_widths(chip):
    # the benchmark cell's layer0/kernel: compression 32, d=10, K=10,
    # each 256-row block gathered in sub-blocks
    spec = make_qspec(1, (784, 300), 784, compression=32, d=10, window=128,
                      seed=0)
    assert build_block_plan(spec, 256).nsub > 1
    text = _compile(
        chip, lambda G: qz_reconstruct.qz_reconstruct_batched_bwd_plan(
            spec, G, interpret=False), ((K, spec.m), jnp.float32))
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1


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


def _reader_pattern(name):
    """``PATTERN`` of a benchmark reader, read from its own file."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, ROOT / "bench" / "metrics" / (name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.PATTERN


def test_round_trace_names(chip, monkeypatch):
    """The federated round at reduced widths (784-20-20-10, K=2, E=2),
    as the benchmark's k10 cell configures it: one fused-forward and one
    plan-backward kernel per zampled tensor, named as the readers
    expect and under no scope; every program scope on some other op."""
    from repro import tracing
    from repro.core import FederatedConfig, ZamplingConfig, build_specs
    from repro.kernels import ops
    from repro.models.mlp import init_mlp_params, mlp_loss
    from repro.train import federated_fit

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_DEFAULT_IMPL", "pallas")
    dims, k, e, b = (784, 20, 20, 10), 2, 2, 8
    template = jax.eval_shape(lambda key: init_mlp_params(key, dims),
                              jax.random.PRNGKey(0))
    zspecs = build_specs(template, ZamplingConfig(
        compression=32, d=10, window=128, seed=0, min_size=128))
    fcfg = FederatedConfig(num_clients=k, local_steps=e, local_lr=0.5,
                           aggregate="psum_u32", downlink="u8")

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    leaves = {"/".join(str(getattr(q, "key", q)) for q in path): leaf
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  template)[0]}
    state = {"scores": {p: on_chip((s.n,), jnp.uint8)
                        for p, s in zspecs.specs.items()},
             "dense": {p: on_chip(leaves[p].shape, leaves[p].dtype)
                       for p in zspecs.dense_paths}}
    batches = {"x": on_chip((1, k, e, b, dims[0]), jnp.float32),
               "y": on_chip((1, k, e, b), jnp.int32)}

    def fit(s, bt, key):
        return federated_fit(zspecs, s, mlp_loss, bt, key, fcfg)

    with jax.default_matmul_precision("highest"):
        text = jax.jit(fit).lower(state, batches,
                                  on_chip((2,), jnp.uint32)).compile(
        ).as_text()
    lines = [re.sub(r"^ROOT ", "", ln.strip()) for ln in text.splitlines()]
    tensors = len(zspecs.specs)
    for reader in ("reconstruct_roofline", "bwd_plan_roofline"):
        rx = re.compile(_reader_pattern(reader))
        kernels = [ln for ln in lines if rx.search(ln)]
        assert len(kernels) == tensors, (reader, kernels)
        for ln in kernels:
            op_name = OP_NAME.search(ln).group(1)
            assert op_name.endswith("pallas_call"), op_name
            assert not any(sc in op_name for sc in tracing.SCOPES), op_name
    found = {sc for ln in lines for m in [OP_NAME.search(ln)] if m
             for sc in tracing.SCOPES if sc in m.group(1)}
    assert found == set(tracing.SCOPES)
