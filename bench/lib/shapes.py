"""Operations and bytes the federated round's algorithm needs, from
its shapes alone.

Counts are what the algorithm requires, never what a particular
kernel layout happens to do (one-hot passes, padding and recomputation
are not counted), so a share of a peak computed from them cannot pass
100% unless the time leaves work out.

``layout`` is a list of zampled tensors, each a dict with ``m``
(weights), ``n`` (scores) and ``d`` (edges per weight row).
"""

from __future__ import annotations


def reconstruct_work(layout, clients: int):
    """One fused sample+reconstruct call over every zampled tensor for
    ``clients`` clients: read the n f32 scores, write the m f32
    weights, 2·d operations (multiply, add) per weight."""
    flops = sum(2 * t["d"] * t["m"] for t in layout) * clients
    nbytes = sum(4 * (t["n"] + t["m"]) for t in layout) * clients
    return flops, nbytes


def plan_backward_work(layout, clients: int):
    """One transpose ``Qᵀ g`` over every zampled tensor: read the m f32
    weight cotangents, write the n f32 score gradients, 2·d operations
    per weight."""
    flops = sum(2 * t["d"] * t["m"] for t in layout) * clients
    nbytes = sum(4 * (t["m"] + t["n"]) for t in layout) * clients
    return flops, nbytes


def round_model_flops(layout, n_params: int, clients: int,
                      local_steps: int, batch: int):
    """Operations one federated round requires: 6·N per sample for the
    model's forward and backward over K·E·B samples, plus the Q·z
    reconstruction and its transpose (2·d·m each) per client-step."""
    samples = clients * local_steps * batch
    qz = sum(2 * 2 * t["d"] * t["m"] for t in layout)
    return 6 * n_params * samples + qz * clients * local_steps


def roofline_share(flops: float, nbytes: float, seconds: float, peaks):
    """Least time the chip could take over the time taken, and which
    bound sets it ('compute' or 'memory')."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_c, t_m) / seconds, ("compute" if t_c >= t_m else "memory")
