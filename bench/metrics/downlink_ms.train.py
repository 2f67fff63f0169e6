"""Device milliseconds per round in op events under the program's
``fed.downlink`` scope (the server's u8 broadcast encode and each
client's decode), summed over each chip's events and averaged over the
chips. Nothing when no event of the window carries the scope."""

from bench.lib.scopes import scope_ms

SCOPE = "fed.downlink"


def read(ctx):
    return scope_ms(ctx, SCOPE)
