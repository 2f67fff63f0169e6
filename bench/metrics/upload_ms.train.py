"""Device milliseconds per round in op events under the program's
``fed.upload`` scope (the end-of-round upload draw and lane pack),
summed over each chip's events and averaged over the chips. Nothing
when no event of the window carries the scope."""

from bench.lib.scopes import scope_ms

SCOPE = "fed.upload"


def read(ctx):
    return scope_ms(ctx, SCOPE)
