"""CPU self-check of the yardstick: the trace reduction, the shape
arithmetic, and the rule that a run without a TPU prints no result.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_selfcheck.py
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.lib import shapes
from bench.lib.peaks import PEAKS, lookup
from bench.lib.trace import Trace, gaps, op_kind, short_name, union_length

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).with_name("data") / "small.xplane.pb"


def synthetic():
    # window [0, 100] ns; chip 0 busy [10, 30] ∪ [20, 40] ∪ [60, 70]
    ops = {0: [(10, 30, "fusion"), (20, 40, "_sbfwd_kernel"),
               (60, 70, "all-reduce.1"), (150, 160, "outside")],
           1: [(0, 100, "fusion")]}
    spans = [(0, 100, "bench.window"), (0, 50, "bench.fit"),
             (50, 100, "bench.wait"), (40, 60, "bench.feed")]
    modules = {0: [(80, 95, "jit_fit")]}
    return Trace(ops, spans, modules)


def test_union_and_gaps():
    assert union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_length([]) == 0
    assert gaps([(10, 30), (20, 40), (60, 70)], 0, 100) == [
        (0, 10), (40, 60), (70, 100)]


def test_trace_reduction():
    t = synthetic()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s(0) == pytest.approx(40e-9)  # 30 (10..40) + 10
    assert t.busy_s(1) == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(70e-9)  # mean over chips
    assert t.summed_s("sbfwd") == (pytest.approx(20e-9), 1)
    assert t.summed_s("all-reduce") == (pytest.approx(10e-9), 1)
    # gaps of chip 0: [0,10] in fit, [40,60] in feed (innermost),
    # [70,100] with its middle inside the program jit_fit
    got = dict((n, v) for n, v in t.idle_gaps())
    assert got == {"in jit_fit": pytest.approx(30e-9),
                   "bench.feed": pytest.approx(20e-9),
                   "bench.fit": pytest.approx(10e-9)}
    top = t.top_ops()
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(20e-9)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three calls of a small jitted
    program, each in a ``bench.fit`` span, inside ``bench.window``."""
    t = Trace.from_file(str(RECORDED))
    assert t.chips == [0]
    assert 0 < t.busy_s() < t.window_s
    assert t.span_count("bench.fit") == 3
    ops = t.top_ops()
    assert ops and all(v > 0 for _, v in ops)
    assert sum(v for _, v in t.idle_gaps()) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-6)


MNISTFC = [{"m": 235200, "n": 7424, "d": 10}, {"m": 30000, "n": 1024, "d": 10},
           {"m": 1000, "n": 32, "d": 10}]


def test_op_names():
    """Event names as the v5e trace gives them, and the kernel readers'
    patterns against them."""
    fwd = ('%jvp__.21 = f32[10,237568]{1,0:T(8,128)S(1)} custom-call('
           'f32[10,7424]{1,0} %b, u32[10,1]{1,0} %s), '
           'custom_call_target="tpu_custom_call"')
    loop = '%while.20 = (s32[], f32[10,300]{1,0}) while((s32[]) %t)'
    assert op_kind(fwd) == "custom-call" and op_kind(loop) == "while"
    assert short_name(fwd) == "%jvp__.21 custom-call"
    pats = {}
    for name in ("reconstruct_roofline", "bwd_plan_roofline"):
        src = (ROOT / "bench" / "metrics" / f"{name}.py").read_text()
        pats[name] = re.search(r'PATTERN = r"(.*)"', src).group(1)
    bwd = fwd.replace("%jvp__.21", "%transpose_jvp___.21")
    assert re.search(pats["reconstruct_roofline"], fwd)
    assert not re.search(pats["reconstruct_roofline"], bwd)
    assert re.search(pats["bwd_plan_roofline"], bwd)
    assert not re.search(pats["bwd_plan_roofline"], fwd)


def test_shapes():
    flops, nbytes = shapes.reconstruct_work(MNISTFC, clients=10)
    assert flops == 2 * 10 * 266200 * 10
    assert nbytes == 4 * (266200 + 8480) * 10
    assert shapes.plan_backward_work(MNISTFC, 1) == (
        2 * 10 * 266200, 4 * (266200 + 8480))
    per_round = shapes.round_model_flops(MNISTFC, 266610, 10, 100, 64)
    assert per_round == 6 * 266610 * 64000 + 4 * 10 * 266200 * 1000
    share, bound = shapes.roofline_share(0.0, 819e9, 2.0,
                                         lookup("TPU v5 lite"))
    assert (share, bound) == (pytest.approx(0.5), "memory")
    share, bound = shapes.roofline_share(197e12, 0.0, 4.0,
                                         PEAKS["TPU v5 lite"])
    assert (share, bound) == (pytest.approx(0.25), "compute")
    with pytest.raises(KeyError):
        lookup("TPU v0 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fed.mnistfc.k10",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_no_tpu_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
