"""Share of the traced window in which no op ran on the device (%),
averaged over the cell's devices."""
from bench.context import busy_seconds, per_device_mean


def read(ctx):
    busy = per_device_mean(busy_seconds(ctx))
    return 100.0 * (1.0 - busy / ctx.window_s)
