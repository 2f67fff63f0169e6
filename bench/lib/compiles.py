"""The program's compile records (``repro.tracing``) set against the
traced window.

Each record is ``(event, start_ns, end_ns)`` on the wall clock that the
profiler stamps host events with.  Less the profile's start time
(``scopes.profile_start_ns``), the origin of the trace's event times,
it compares directly with the window's bounds ``trace.lo`` /
``trace.hi``.  JAX times a persistent-cache retrieval inside the
backend-compile event that asked for it, and traces nested jitted
functions inside the outer trace, so time is the length of the union of
the records, and a retrieval counts as a compile only where no
backend-compile record holds it.
"""

from __future__ import annotations

import sys
from typing import Optional

from bench.lib.scopes import profile_start_ns, window_file
from bench.lib.trace import union_length


def _records(ctx):
    """(the program's counter module, its records on the trace's clock),
    or nothing when the program has no counter."""
    try:
        from repro import tracing
    except ImportError:
        return None, None
    if "profile_start_ns" not in ctx:
        path = window_file()
        ctx["profile_start_ns"] = profile_start_ns(path) if path else None
    t0 = ctx["profile_start_ns"]
    if t0 is None:
        return None, None
    records = [(e, s - t0, f - t0) for e, s, f in tracing.records()]
    if not ctx.get("compiles_reported"):
        ctx["compiles_reported"] = True
        _report(tracing, records, ctx["trace"].lo)
    return tracing, records


def _report(tracing, records, lo: float) -> None:
    """One line on standard error: the records before the window, by
    event (count, union seconds, longest), and how far before the window
    the first began and the last ended."""
    before = [r for r in records if r[2] <= lo]
    if not before:
        return
    parts = []
    for name in tracing.EVENTS:
        rs = [(r[1], r[2]) for r in before if r[0] == name]
        if rs:
            parts.append(
                f"{name.rsplit('/', 1)[-1]} {len(rs)} (union "
                f"{union_length(rs) * 1e-9:.3f} s, longest "
                f"{max(e - s for s, e in rs) * 1e-9:.3f} s)")
    print(f"bench: compile records before the window: {'; '.join(parts)}; "
          f"first began {(lo - min(r[1] for r in before)) * 1e-9:.3f} s and "
          f"last ended {(lo - max(r[2] for r in before)) * 1e-9:.3f} s "
          f"before it", file=sys.stderr)


def compiles_in_window(ctx) -> Optional[int]:
    """Backend compiles and cache retrievals that overlap the window."""
    tracing, records = _records(ctx)
    if tracing is None:
        return None
    t = ctx["trace"]
    recs = [r for r in records if r[2] >= t.lo and r[1] <= t.hi]
    compiles = [r for r in recs if r[0] == tracing.COMPILE_EVENT]
    loose = [r for r in recs if r[0] == tracing.CACHE_EVENT
             and not any(c[1] <= r[1] and r[2] <= c[2] for c in compiles)]
    return len(compiles) + len(loose)


def seconds_before_window(ctx, events, less=()) -> Optional[float]:
    """Seconds of the union of the records of ``events`` (names of
    ``repro.tracing``) that ended before the window opened, less the
    time in which a record of ``less`` ran too (a compile made while a
    function is traced runs inside its trace record)."""
    tracing, records = _records(ctx)
    if tracing is None:
        return None
    lo = ctx["trace"].lo

    def covered(names):
        names = {getattr(tracing, e) for e in names}
        return union_length((r[1], r[2]) for r in records
                            if r[0] in names and r[2] <= lo)

    return (covered((*events, *less)) - covered(less)) * 1e-9
