"""Compiles inside the traced window: the program's backend-compile
and persistent-cache-retrieval records (``repro.tracing``) that overlap
``[trace.lo, trace.hi]``.  Nothing when the program has no counter."""

from bench.lib.compiles import compiles_in_window


def read(ctx):
    return compiles_in_window(ctx)
