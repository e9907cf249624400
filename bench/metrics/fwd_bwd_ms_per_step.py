"""Device time of the forward and backward pass per local step (ms): leaf
ops under the program's ``fwd_bwd`` scope (the model's loss and gradient,
rematerialised forward included), averaged over devices."""
from bench.context import per_device_mean

SCOPE = "fwd_bwd"


def read(ctx):
    secs = per_device_mean(ctx.scoped_seconds((SCOPE,)))
    if secs <= 0:
        return None
    return 1e3 * secs / (ctx.rounds * ctx.comm_period)
