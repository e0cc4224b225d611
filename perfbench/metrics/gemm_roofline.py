"""gemm_roofline (%): the least time of the convs that run on a GEMM
kernel, im2col (``L<i>.conv.im2col_gemm``, VGG-16's fc1 among them) or
direct 1x1 (``L<i>.conv.direct``), over the Pallas kernel time inside their
layer scopes, per forward per chip.

Least time is counted from shapes (``harness.work``) at the batch one chip
runs; kernel time is read from the trace's layer scopes
(``harness.scopes``).  Moves ``images_per_s``.
"""
from harness import scopes


def read(ctx):
    return scopes.family_roofline(ctx, ("im2col_gemm", "direct"))
