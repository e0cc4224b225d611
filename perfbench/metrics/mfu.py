"""mfu (%): the whole forward's share of the chip's peak.

Floor operations of every layer per image (``harness.work``), each over the
peak of its layer's dtype, times the images per second completed in the
traced run's window, over the chips.  Moves ``images_per_s``.
"""
from harness.work import image_peak_seconds


def read(ctx):
    if not ctx.window.completed:
        return None
    per_image = image_peak_seconds(ctx.work, ctx.dtypes, ctx.peaks)
    return 100.0 * per_image * ctx.window.images_per_s / ctx.chips
