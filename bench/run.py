"""Run one benchmark cell once and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``:
its configuration (``bench/configs/<config>.json``, whose ``system``
names the driver in ``bench/systems/`` and whose ``reference`` names
the plain reference beside it), its traffic mix
(``bench/traffic/<traffic>.json``), its own settings and limits
(``bench/cells/<cell>.json``) and each per-layer metric's reader
(``bench/metrics/<metric>.py``).

A run: set-up (JAX start, inputs and weights from the seed on the
device, compile or cache load, warm-up: ``setup_s``), a window of
``--seconds``, the peak device memory, then the comparison with the
plain reference that decides ``correct``.  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are read
from its trace instead of the end-to-end ones.  The last line of
standard output is one JSON object; the numbers compared, each beside
its limit, are the last lines of standard error and the last key of
that object.

A run that finds no TPU, or fewer chips than the cell asks for, exits
with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    pass


def load_json(*parts):
    with open(BENCH.joinpath(*parts)) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, bench=None):
    """(workload entry, config, traffic, cell settings, benchmark), from
    ``BENCHMARK.json`` unless ``bench`` is given."""
    if bench is None:
        with open(ROOT / "BENCHMARK.json") as f:
            bench = json.load(f)
    wl = next((w for w in bench["workloads"] if w["name"] == workload),
              None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg = load_json("configs", wl["config"] + ".json")
    traffic = load_json("traffic", wl["traffic"] + ".json")
    cell = load_json("cells", workload + ".json")
    return wl, cfg, traffic, cell, bench


def metrics_of(bench, section: str, workload: str):
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


class Harness:
    """What a system driver may use: the clock, host spans that land in
    the profiler's trace, and the configuration's reference."""

    now = staticmethod(time.perf_counter)

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def load_reference(self, cfg):
        return load_module(BENCH / "configs" / cfg["reference"],
                           "bench_reference_" + cfg["name"].replace(
                               "-", "_").replace(".", "_"))


def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout, with
    no size cap (an LRU cap below the size of one cell's programs
    evicts every entry before it is read again)."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(chips: int, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def read_trace(run, wl, bench, peaks, window):
    """Per-layer metrics, device busy/window seconds and the breakdown
    from the trace of the window just run."""
    from bench.lib.trace import Trace

    files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    trace = Trace.from_file(str(files[-1]))
    ctx = {"trace": trace, "work": run.work(), "peaks": peaks,
           "window": window}
    out = {}
    for m in metrics_of(bench, "per_layer", wl["name"]):
        reader = load_module(BENCH / "metrics" / (m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": trace.busy_s(), "window_s": trace.window_s}
    breakdown = {"device_ops": trace.top_ops(10),
                 "idle_gaps": trace.idle_gaps(10)}
    return out, device, breakdown


def run_cell(wl, cfg, traffic, cell, bench, *, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True):
    """Set up, measure, check; returns the result dict."""
    import jax

    devices = chips_for(wl["chips"], require_tpu)
    kind = devices[0].device_kind
    from bench.lib.peaks import lookup
    peaks = lookup(kind) if require_tpu else None
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    system = load_module(BENCH / "systems" / (cfg["system"] + ".py"),
                         "bench_system_" + cfg["system"])
    h = Harness()
    run = system.Run(h, cfg, traffic, cell, seed, devices)
    setup_s = h.now() - T_START

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with h.span("bench.window"):
                window = run.window(min(seconds, cell["trace_seconds"]))
        finally:
            jax.profiler.stop_trace()
        metrics, dev_extra, breakdown = read_trace(run, wl, bench, peaks,
                                                   window)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        window = run.window(seconds)
        e2e = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(bench, "end_to_end", wl["name"])}
        dev_extra, breakdown = {}, None

    memory = peak_memory(devices)
    t_check = h.now()
    checks = run.check()
    print(f"bench: window {window['elapsed_s']:.3f} s, set-up {setup_s:.3f} s, "
          f"check {h.now() - t_check:.3f} s", file=sys.stderr)
    correct = all(v == v and v <= lim for _, v, lim in checks)
    result = {
        "correct": bool(correct),
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": memory,
                   **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    wl, cfg, traffic, cell, bench = find_cell(args.workload)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    enable_compile_cache()
    try:
        result = run_cell(wl, cfg, traffic, cell, bench, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
