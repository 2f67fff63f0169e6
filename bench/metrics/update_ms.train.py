"""Device milliseconds per round in op events under the program's
``fed.update`` scope (the optimizer step on each client's trainable
copy), summed over each chip's events and averaged over the chips.
Nothing when no event of the window carries the scope."""

from bench.lib.scopes import scope_ms

SCOPE = "fed.update"


def read(ctx):
    return scope_ms(ctx, SCOPE)
