"""Device time of the DEPOSITUM update per local step (ms): ops under the
``fused_kernel`` (Pallas) or ``local_step`` (jnp) scope, averaged over
devices."""
from bench.context import UPDATE_SCOPES, per_device_mean


def read(ctx):
    secs = per_device_mean(ctx.scoped_seconds(UPDATE_SCOPES))
    if secs <= 0:
        return None
    return 1e3 * secs / (ctx.rounds * ctx.comm_period)
