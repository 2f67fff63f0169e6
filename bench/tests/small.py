"""A whole benchmark run at a size the CPU can hold, for the tests."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SMALL = {"dims": [784, 20, 20, 10]}

# cells whose files are in bench/ but which BENCHMARK.json does not list
# yet (not measured on the chip)
PENDING = [
    {"name": "fed.mnistfc.sharded4", "config": "mnistfc",
     "traffic": "fed_iid_k4_e100_b64_sharded", "chips": 4, "why": ""},
]


def find_cell(workload: str):
    """``run.find_cell``, knowing the pending cells too."""
    import json

    from bench import run

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = bench["workloads"] + PENDING
    return run.find_cell(workload, bench)
SMALL_TRAFFIC = {"local_steps": 4, "batch": 8}


def run_small(workload: str, seconds: float = 0.5, seed: int = 2**31 + 7):
    """``run_cell`` on the CPU: the cell's own configuration, traffic
    and limits, with the network and the local work cut down."""
    from bench import run

    wl, cfg, traffic, cell, bench = find_cell(workload)
    cfg = dict(cfg, **SMALL)
    traffic = dict(traffic, **SMALL_TRAFFIC,
                   clients=min(traffic["clients"], 4))
    return run.run_cell(wl, cfg, traffic, cell, bench, seed=seed,
                        seconds=seconds, trace=False, require_tpu=False)
