"""Model FLOP utilisation of the whole training step (%): the forward and
backward matmul FLOPs per token (``bench/flops/<family>.py``) times the
traced window's tokens per second, over chips times the bf16 peak."""
from bench.spec import flops_module


def read(ctx):
    fpt = flops_module(ctx.model["family"]).train_flops_per_token(
        ctx.model, ctx.seq_len)
    rate = ctx.tokens / ctx.window_s
    return 100.0 * fpt * rate / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
