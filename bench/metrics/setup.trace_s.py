"""Seconds of set-up spent tracing to jaxprs and lowering to MLIR: the
union of the program's trace and lowering records (``repro.tracing``)
that ended before the window, less the time in which a compile or a
cache load ran inside them (``setup.compile_s`` counts that), so that
the two readings add up.  Nothing when the program has no counter."""

from bench.lib.compiles import seconds_before_window


def read(ctx):
    return seconds_before_window(ctx, ("TRACE_EVENT", "LOWER_EVENT"),
                                 less=("COMPILE_EVENT", "CACHE_EVENT"))
