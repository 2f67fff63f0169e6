"""A run with the timed path broken underneath comes out not correct.

Runs on the CPU at a small size (Pallas in interpret mode), skipping
only the harness's look for a TPU; everything else is a whole run:
set-up, window, comparison with the plain reference.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m pytest -q bench/tests/test_faults.py
"""

from __future__ import annotations

import pytest

from bench.lib.faults import planted
from bench.tests.small import run_small


def _need_devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")


@pytest.mark.parametrize("workload", ["fed.mnistfc.k10",
                                      "fed.mnistfc.sharded4"])
def test_sound_run_is_correct(workload):
    _need_devices(4 if workload.endswith("4") else 1)
    res = run_small(workload)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("workload,fault", [
    ("fed.mnistfc.k10", "state_unchanged"),
    ("fed.mnistfc.k10", "half_batch"),
    ("fed.mnistfc.k10", "upload_altered"),
    ("fed.mnistfc.sharded4", "exchange_left_out"),
])
def test_fault_is_caught(workload, fault):
    _need_devices(4 if workload.endswith("4") else 1)
    with planted(fault):
        res = run_small(workload)
    assert not res["correct"], res["checks"]
