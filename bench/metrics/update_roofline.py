"""The DEPOSITUM update's share of its HBM roofline (%): the bytes it must
move per local step on one device over its device time per step (the
``update_ms_per_step`` ops), over the HBM peak.  The update does about one
FLOP per byte, so bandwidth bounds it."""
from bench.context import UPDATE_SCOPES, per_device_mean

#: model-sized sweeps per client and local step: the momentum + prox
#: kernel reads x, y, nu and writes x, nu; the tracking kernel reads y,
#: g_new, g_old and writes y, g
SWEEPS = 10


def bytes_per_step(client_leaf_bytes, clients_per_device: int) -> float:
    return SWEEPS * clients_per_device * float(sum(client_leaf_bytes))


def read(ctx):
    secs = per_device_mean(ctx.scoped_seconds(UPDATE_SCOPES))
    if secs <= 0:
        return None
    per_step = secs / (ctx.rounds * ctx.comm_period)
    need = bytes_per_step(ctx.client_leaf_bytes, ctx.clients_per_device)
    return 100.0 * need / per_step / ctx.peaks["hbm_bytes_per_s"]
