"""The control comes out as not correct; the program does not.

The control is the configuration's plain reference put in the
program's place at the precision below the configuration's: Q's values
and every other contraction's operands rounded to 16 significant bits,
what a three-pass bfloat16 product keeps of float32 (the configuration
states float32 at 'highest').  Here it runs on the CPU at the
published widths with few clients and local steps, and the program on
its XLA path; ``bench/control.py`` reads the same numbers on the chip
at the cell's own size.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m pytest -q bench/tests/test_control.py
"""

from __future__ import annotations

import pytest

from bench.tests.small import find_cell

TRAFFIC = {"local_steps": 20, "batch": 32}


def readings(workload: str, seed: int):
    import jax

    from bench import run

    wl, cfg, traffic, cell, _ = find_cell(workload)
    if len(jax.devices()) < wl["chips"]:
        pytest.skip(f"needs {wl['chips']} devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")
    cfg = dict(cfg, impl="ref")
    traffic = dict(traffic, **TRAFFIC,
                   clients=wl["chips"] if wl["chips"] > 1 else 2)
    system = run.load_module(run.BENCH / "systems" / "federated.py",
                             "bench_system_federated")
    r = system.Run(run.Harness(), cfg, traffic, cell, seed,
                   jax.devices()[:wl["chips"]])
    r.free()
    return cell["limits"], r.readings(control=True)


@pytest.mark.parametrize("workload,seed", [
    ("fed.mnistfc.k10", 11), ("fed.mnistfc.k10", 2**31 + 12),
    ("fed.mnistfc.k10", 13), ("fed.mnistfc.sharded4", 14)])
def test_control_fails_program_passes(workload, seed):
    limits, got = readings(workload, seed)
    prog, ctrl = got["program"], got["control"]
    assert all(prog[k] <= lim for k, lim in limits.items()), prog
    assert any(ctrl[k] > lim for k, lim in limits.items()), ctrl
