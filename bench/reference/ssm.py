"""Reference for the attention-free Mamba-2 language model.

Embedding (tied with the output head), ``n_layers`` pre-norm Mamba-2
blocks with residuals, a final RMS norm, logits over the padded
vocabulary, mean cross-entropy.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import (Numerics, cross_entropy, mamba2_block,
                                    padded_vocab, rms_norm)


def mamba_leaves(cfg: dict, lead: tuple, prefix: tuple):
    """(path, shape, law) of the Mamba-2 blocks, stacked on ``lead``."""
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    N, P, W = cfg["ssm_state"], cfg["ssm_head_dim"], cfg["ssm_conv_width"]
    H = di // P
    conv = di + 2 * N
    m = prefix + ("mamba",)
    return [
        (prefix + ("ln", "scale"), lead + (d,), ("zeros",)),
        (m + ("in_proj",), lead + (d, 2 * di + 2 * N + H),
         ("normal", 1 / math.sqrt(d))),
        (m + ("conv_w",), lead + (W, conv), ("normal", 0.5)),
        (m + ("conv_b",), lead + (conv,), ("zeros",)),
        (m + ("dt_bias",), lead + (H,), ("dt_bias", 1e-3, 0.1)),
        (m + ("A_log",), lead + (H,), ("a_log", 1.0, 16.0)),
        (m + ("D",), lead + (H,), ("ones",)),
        (m + ("norm",), lead + (di,), ("zeros",)),
        (m + ("out_proj",), lead + (di, d), ("normal", 1 / math.sqrt(di))),
    ]


def leaves(cfg: dict):
    d, V = cfg["d_model"], padded_vocab(cfg)
    return mamba_leaves(cfg, (cfg["n_layers"],), ("blocks",)) + [
        (("embed", "table"), (V, d), ("normal", 0.02)),
        (("final_norm", "scale"), (d,), ("zeros",)),
    ]


def mamba_layer(cfg: dict, num: Numerics):
    """h -> h + Mamba2(RMSNorm(h)), rematerialised in the backward pass."""
    @jax.checkpoint
    def body(h, layer):
        return h + mamba2_block(layer["mamba"], rms_norm(h, layer["ln"]["scale"]),
                                cfg, num), None
    return body


def loss(params, tokens, labels, cfg: dict, num: Numerics):
    h = params["embed"]["table"][tokens]
    h, _ = jax.lax.scan(mamba_layer(cfg, num), h, params["blocks"])
    h = rms_norm(h, params["final_norm"]["scale"])
    logits = num.mm("bld,vd->blv", h, params["embed"]["table"])
    return cross_entropy(logits, labels)
