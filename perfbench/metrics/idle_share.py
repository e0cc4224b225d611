"""idle_share (%): the share of the traced window in which no op ran on a
chip, averaged over the cell's chips.  Moves ``images_per_s``."""


def read(ctx):
    r = ctx.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.mean_busy_s / r.window_s)
