"""winograd_glue_share (%): device time of the ops that are not Pallas
kernels inside the Winograd convs' layer scopes (``L<i>.conv.winograd``:
tiling copies, input transforms, pads), over device busy time, all chips
together.  A part of ``xla_share``.  Moves ``images_per_s``.
"""
from harness import scopes


def read(ctx):
    sc = scopes.for_run(ctx)
    busy = ctx.reduced.total_busy_s if ctx.reduced is not None else 0.0
    if sc is None or busy <= 0:
        return None
    glue = sum(t.glue_s for t in sc.layers().values()
               if t.kind == "conv" and t.algorithm == "winograd")
    return 100.0 * glue / busy
