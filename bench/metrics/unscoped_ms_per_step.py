"""Device time per local step (ms) of the window's leaf ops under none of
the program's scopes, averaged over devices: copies, instructions the
compiler made without an ``op_name``, and programs other than the round
(the trainer's loss readback).  With ``fwd_bwd_ms_per_step``,
``update_ms_per_step`` and ``gossip_ms_per_round`` / T0 it adds up to the
window's leaf-op device time per step.  Nothing to read where the program
does not name its forward and backward pass."""
from bench.context import per_device_mean
from bench.metrics.fwd_bwd_ms_per_step import SCOPE as FWD_BWD

#: every scope the program puts on device ops
SCOPES = ("fwd_bwd", "fused_kernel", "local_step", "gossip", "compress_pack",
          "compress_unpack", "telemetry")


def leaf_seconds(ctx) -> list[float]:
    """Per device: summed duration of the leaf ops inside the window."""
    lo, hi = ctx.trace.window()
    out = []
    for dev in ctx.trace.devices:
        total = 0.0
        for s, e, leaf in zip(dev.start, dev.end, dev.leaf):
            if leaf and lo <= s < hi:
                total += e - s
        out.append(total * 1e-9)
    return out


def read(ctx):
    if per_device_mean(ctx.scoped_seconds((FWD_BWD,))) <= 0:
        return None
    unscoped = [t - s for t, s in zip(leaf_seconds(ctx),
                                      ctx.scoped_seconds(SCOPES))]
    return 1e3 * per_device_mean(unscoped) / (ctx.rounds * ctx.comm_period)
