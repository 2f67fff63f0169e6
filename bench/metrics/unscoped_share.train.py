"""Share of device busy time, in percent, in op events that are neither
one of the two kernels (by their readers' patterns) nor under a program
scope: what the scoped metrics cannot name."""

from bench.lib.scopes import unscoped_share


def read(ctx):
    return unscoped_share(ctx)
