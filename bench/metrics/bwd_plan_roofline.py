"""Share of its roofline that the plan backward ``Qᵀ g`` kernel
reaches, in percent.

Time: the summed device time of the kernel's ``tpu_custom_call``
events, found by ``PATTERN``.  Work: read the m f32 weight
cotangents, write the n score gradients, 2·d operations per weight
(``bench.lib.shapes.plan_backward_work``), one launch per zampled
tensor per local step."""

from bench.lib.kernel_share import kernel_roofline
from bench.lib.shapes import plan_backward_work

# the transpose of that custom_vjp: HLO name "transpose(jvp(...))"
PATTERN = r"^%transpose_jvp_+[.\d]* = f32\[.*tpu_custom_call"


def read(ctx):
    return kernel_roofline(ctx, PATTERN, plan_backward_work)
