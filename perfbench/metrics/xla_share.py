"""xla_share (%): device time of ops that are not Pallas kernels (pads,
crops, Winograd tiling transposes, pools, shortcuts, the classifier's
dots), over device busy time, all chips together.  Moves ``images_per_s``.
"""


def read(ctx):
    r = ctx.reduced
    if r is None or r.total_busy_s <= 0:
        return None
    return 100.0 * (r.total_busy_s - r.total_kernel_s) / r.total_busy_s
