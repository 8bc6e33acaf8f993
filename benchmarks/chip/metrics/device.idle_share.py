"""device.idle_share: the share of the traced window in which no op ran
on the device, averaged over the cell's chips."""
from bench import trace as T


def read(ctx):
    return 100.0 * (1.0 - T.mean_busy_s(ctx.trace) / T.window_s(ctx.trace))
