"""Device milliseconds per round spent in collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all),
summed over each chip's events and averaged over the chips."""

PATTERN = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"


def read(ctx):
    t = ctx["trace"]
    rounds = ctx["window"]["units"]
    secs, n = t.summed_s(PATTERN)
    if n == 0 or rounds <= 0:
        return None
    return secs * 1e3 / len(t.chips) / rounds
