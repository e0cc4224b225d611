"""winograd_roofline (%): the least time of the convs that run on the fused
Winograd kernel, over the Pallas kernel time inside their layer scopes
(``L<i>.conv.winograd``), per forward per chip.

Least time is counted from shapes (``harness.work``) at the batch one chip
runs; kernel time is read from the trace's layer scopes
(``harness.scopes``).  Moves ``images_per_s``.
"""
from harness import scopes


def read(ctx):
    return scopes.family_roofline(ctx, ("winograd",))
