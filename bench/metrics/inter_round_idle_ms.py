"""Device idle time between one round program's last op and the next one's
first op (ms), over the gaps between consecutive rounds in the window:
the gap less the time other programs' ops ran in it, averaged over gaps
and devices."""
import numpy as np

from bench.context import per_device_mean
from bench.trace import union_length


def read(ctx):
    lo, hi = ctx.trace.window()
    per_dev = []
    for dev in ctx.trace.devices:
        name = ctx.round_module(dev)
        runs = [(s, e) for n, s, e in dev.modules if n == name and lo <= s < hi]
        if len(runs) < 2:
            return None
        idle = [(b - a) - union_length(dev.start, dev.end, a, b)
                for (_, a), (b, _) in zip(runs[:-1], runs[1:])]
        per_dev.append(float(np.mean(idle)) * 1e-6)
    return per_device_mean(per_dev)
