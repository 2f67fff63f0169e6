"""The federated round's required operations over the traced window's
time and the chips' bf16 peak, in percent.

Operations per round (``bench.lib.shapes.round_model_flops``): 6·N per
sample over K·E·B samples, plus Q·z and its transpose per client-step.
Recomputation is not counted."""

from bench.lib.shapes import round_model_flops


def read(ctx):
    w, t = ctx["work"], ctx["trace"]
    rounds = ctx["window"]["units"]
    if rounds <= 0 or not t.chips:
        return None
    flops = rounds * round_model_flops(w["layout"], w["n_params"],
                                       w["clients"], w["local_steps"],
                                       w["batch"])
    return 100.0 * flops / (t.window_s * ctx["peaks"]["bf16_flops"]
                            * len(t.chips))
