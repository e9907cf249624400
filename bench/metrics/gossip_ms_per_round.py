"""Device time of the gossip per round (ms): ops under the ``gossip`` scope
(the shard_map collectives and their waits), averaged
over devices."""
from bench.context import GOSSIP_SCOPE, per_device_mean


def read(ctx):
    secs = per_device_mean(ctx.scoped_seconds((GOSSIP_SCOPE,)))
    if secs <= 0:
        return None
    return 1e3 * secs / ctx.rounds
