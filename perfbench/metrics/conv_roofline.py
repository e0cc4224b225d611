"""conv_roofline (%): the conv layers' least time over their Pallas kernel
time, per forward.

Least time is counted from shapes (``harness.work``) at the batch one chip
runs; kernel time is the union of the Pallas kernel events on each chip in
the traced window, over the executor calls made in it.  Moves
``images_per_s``.
"""
from harness.work import forward_least_time


def read(ctx):
    if ctx.reduced is None or ctx.reduced.kernel_events == 0 or not ctx.window.forwards:
        return None
    per_chip_batch = ctx.batch // ctx.chips
    least = forward_least_time(ctx.work, per_chip_batch, ctx.dtypes, ctx.peaks)
    kernel_per_forward = ctx.reduced.total_kernel_s / (ctx.chips * ctx.window.forwards)
    return 100.0 * least / kernel_per_forward
