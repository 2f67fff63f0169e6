"""Reduce a JAX profiler trace to what the per-layer metrics read.

The harness wraps its traced window in the host span ``bench.window``
and every call into the program in a ``bench.<stage>`` span
(``jax.profiler.TraceAnnotation``), so host spans and device ops sit
on the profiler's one clock.  From the trace this module takes:

- per chip, the device op events (the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane) clipped to the window, without the
  control-flow ops (``while``, ``conditional``, ``call``) whose events
  span their whole body and would hide the gaps inside it;
- busy time: the length of the union of those intervals;
- idle gaps: the holes in that union inside the window.  A hole inside
  a running program (an event of the ``XLA Modules`` line) is named
  ``in <module>``: the device idles between the program's own ops.
  Any other hole is named by the innermost harness span that covers
  its middle: what the host was doing;
- summed device time of the events whose name matches a pattern
  (kernels by their function name, collectives by their op name).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float, str]  # (start_ns, end_ns, name)

# an XLA op event is named by its HLO text: "%name = type kind(operands)..."
OP_KIND = re.compile(r"^%?\S+ = .*?\s([a-z][\w-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    m = OP_KIND.match(name)
    return m.group(1) if m else ""


def short_name(name: str) -> str:
    """``%jvp__.21 custom-call`` for a long HLO event name."""
    kind = op_kind(name)
    return f"{name.split(' = ', 1)[0]} {kind}" if kind else name


def union_length(intervals) -> float:
    """Length of the union of (start, end, ...) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Holes of the union of ``intervals`` inside [lo, hi]."""
    out, t = [], lo
    for s, e, *_ in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


class Trace:
    """Device ops per chip and harness host spans of one traced window."""

    def __init__(self, ops: Dict[int, List[Interval]],
                 spans: List[Interval],
                 modules: Optional[Dict[int, List[Interval]]] = None):
        windows = [s for s in spans if s[2] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
        self.lo, self.hi = windows[0][0], windows[0][1]
        self.spans = [s for s in spans if s[2] != WINDOW_SPAN]
        self.ops = {
            chip: [(max(s, self.lo), min(e, self.hi), n)
                   for s, e, n in evs if e > self.lo and s < self.hi]
            for chip, evs in ops.items()
        }
        self.modules = modules or {}

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        ops: Dict[int, List[Interval]] = {}
        modules: Dict[int, List[Interval]] = {}
        spans: List[Interval] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split("(", 1)[0]) for e in line.events)
                elif m and line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                        if op_kind(e.name) not in CONTAINERS)
                elif not m:
                    spans.extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
        return cls(ops, spans, modules)

    # --- window and busy time --------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy_s(self, chip: Optional[int] = None) -> float:
        """Busy seconds of one chip, or the mean over the chips."""
        chips = self.chips if chip is None else [chip]
        if not chips:
            return 0.0
        return sum(union_length(self.ops[c]) for c in chips) * 1e-9 / len(
            chips)

    # --- events by name --------------------------------------------------
    def events(self, pattern: str, chip: Optional[int] = None):
        rx = re.compile(pattern)
        chips = self.chips if chip is None else [chip]
        return [(c, s, e, n) for c in chips for s, e, n in self.ops[c]
                if rx.search(n)]

    def summed_s(self, pattern: str) -> Tuple[float, int]:
        """(summed device seconds over all chips, event count) of the
        op events whose name matches ``pattern``."""
        evs = self.events(pattern)
        return sum(e - s for _, s, e, _ in evs) * 1e-9, len(evs)

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s[2] == name)

    # --- breakdown -------------------------------------------------------
    def _what_at(self, t: float, chip: int) -> str:
        for s, e, n in self.modules.get(chip, []):
            if s <= t <= e:
                return f"in {n}"
        inner = None
        for s, e, n in self.spans:
            if s <= t <= e and (inner is None or e - s < inner[1] - inner[0]):
                inner = (s, e, n)
        return inner[2] if inner else "no harness span"

    def top_ops(self, k: int = 10, chip: int = 0):
        agg: Dict[str, float] = {}
        for s, e, n in self.ops.get(chip, []):
            n = short_name(n)
            agg[n] = agg.get(n, 0.0) + (e - s) * 1e-9
        return sorted(([n, v] for n, v in agg.items()),
                      key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10, chip: int = 0):
        """The idle time of ``chip`` summed by what it fell in: a running
        program, or the harness span the host was in; longest first."""
        holes = gaps(self.ops.get(chip, []), self.lo, self.hi)
        agg: Dict[str, float] = {}
        for a, b in holes:
            name = self._what_at((a + b) / 2, chip)
            agg[name] = agg.get(name, 0.0) + (b - a) * 1e-9
        return sorted(([n, v] for n, v in agg.items()),
                      key=lambda kv: -kv[1])[:k]
