"""Readings that the limits of a training cell are set from.

  python bench/control.py --workload <cell> --seeds 11,12,13 [--control]
  python bench/control.py --workload <cell> --seeds 11,12,13 --fault half_batch

For each seed: set-up exactly as a benchmark run makes it (the compiled
call driven through its first calls), then the numbers that decide
``correct`` for the program and, with ``--control``, for the control
(the reference at the precision below the configuration's, put in the
program's place); with ``--fault``, the program's with that fault of
``bench/lib/faults.py`` planted.  One JSON line per seed.  No measured window: a
training cell's readings need none.  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

from run import ROOT, Harness, chips_for, enable_compile_cache, find_cell, \
    load_module  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    wl, cfg, traffic, cell, _ = find_cell(args.workload)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    enable_compile_cache()
    devices = chips_for(wl["chips"])
    system = load_module(ROOT / "bench" / "systems" / (cfg["system"] + ".py"),
                         "bench_system_" + cfg["system"])
    from bench.lib.faults import planted

    for seed in (int(s) for s in args.seeds.split(",")):
        with planted(args.fault) if args.fault else contextlib.nullcontext():
            run = system.Run(Harness(), cfg, traffic, cell, seed, devices)
        run.free()
        got = run.readings(control=args.control, detail=True)
        print(json.dumps({"seed": seed, "fault": args.fault, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
