"""Roofline share of one per-local-step kernel of the federated round."""

from __future__ import annotations

from bench.lib.shapes import roofline_share


def kernel_roofline(ctx, pattern: str, work_fn):
    """Percent of the roofline reached by the kernel whose events match
    ``pattern``.  The work of the traced window is one launch per
    zampled tensor per local step per round on every chip; a trace whose
    launch count says otherwise gives nothing."""
    w, t = ctx["work"], ctx["trace"]
    rounds = ctx["window"]["units"]
    secs, count = t.summed_s(pattern)
    launches = rounds * w["local_steps"] * len(w["layout"]) * len(t.chips)
    if count == 0 or count != launches or secs <= 0:
        return None
    flops, nbytes = work_fn(w["layout"], w["clients_per_chip"])
    per_step = rounds * w["local_steps"] * len(t.chips)
    share, _ = roofline_share(flops * per_step, nbytes * per_step, secs,
                              ctx["peaks"])
    return 100.0 * share
