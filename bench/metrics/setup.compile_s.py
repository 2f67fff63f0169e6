"""Seconds of set-up spent in backend compiles and persistent-cache
retrievals: the union of those records of the program's counter
(``repro.tracing``) that ended before the window.  Nothing when the
program has no counter."""

from bench.lib.compiles import seconds_before_window


def read(ctx):
    return seconds_before_window(ctx, ("COMPILE_EVENT", "CACHE_EVENT"))
