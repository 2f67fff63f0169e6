"""Device milliseconds per round in op events under the program's
``fed.model`` scope (the local step outside the two kernels: draw
word, clipped scores, the MLP forward and its backward), summed over
each chip's events and averaged over the chips. Nothing when no event
of the window carries the scope."""

from bench.lib.scopes import scope_ms

SCOPE = "fed.model"


def read(ctx):
    return scope_ms(ctx, SCOPE)
