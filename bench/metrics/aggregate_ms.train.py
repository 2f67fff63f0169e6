"""Device milliseconds per round in op events under the program's
``fed.aggregate`` scope (the server's reduction over the clients:
popcount fold or psum, dense means), summed over each chip's events
and averaged over the chips. Nothing when no event of the window
carries the scope."""

from bench.lib.scopes import scope_ms

SCOPE = "fed.aggregate"


def read(ctx):
    return scope_ms(ctx, SCOPE)
