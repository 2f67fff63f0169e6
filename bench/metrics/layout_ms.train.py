"""Device milliseconds per round in op events under the program's
``qz.layout`` scope (the re-layout of the kernels' operands and results
around each pallas_call: grid-row padding and the sharding-major
moves), summed over each chip's events and averaged over the chips.
Nothing when no event of the window carries the scope."""

from bench.lib.scopes import scope_ms

SCOPE = "qz.layout"


def read(ctx):
    return scope_ms(ctx, SCOPE)
