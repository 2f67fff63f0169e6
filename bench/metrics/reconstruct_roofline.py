"""Share of its roofline that the fused sample+reconstruct kernel
reaches, in percent.

Time: the summed device time of the kernel's ``tpu_custom_call``
events, found by ``PATTERN``.  Work: what the algorithm needs
(``bench.lib.shapes.reconstruct_work``), one launch per zampled tensor
per local step.  Returns nothing when the kernel is not in the trace
or its launches do not match one per tensor per local step."""

from bench.lib.kernel_share import kernel_roofline
from bench.lib.shapes import reconstruct_work

# the fused forward is the primal of the custom_vjp: its HLO name is
# "jvp(...)"; the Pallas kernel carries no name of its own yet
PATTERN = r"^%jvp__[.\d]* = f32\[.*tpu_custom_call"


def read(ctx):
    return kernel_roofline(ctx, PATTERN, reconstruct_work)
