"""Reference for the Zamba2-style hybrid as the program builds it.

Groups of ``shared_attn_every - 1`` pre-norm Mamba-2 blocks, each group
followed by one shared transformer block (the same weights at every
invocation): h += Attn(RMSNorm(h)); h += SwiGLU(RMSNorm(h)).  Untied
output head over the padded vocabulary, mean cross-entropy.
"""
from __future__ import annotations

import math

import jax

from bench.reference.common import (Numerics, causal_attention,
                                    cross_entropy, padded_vocab, rms_norm,
                                    swiglu)
from bench.reference.ssm import mamba_layer, mamba_leaves


def leaves(cfg: dict):
    d, ff, V = cfg["d_model"], cfg["d_ff"], padded_vocab(cfg)
    every = cfg["shared_attn_every"]
    groups = cfg["n_layers"] // every
    s = 1 / math.sqrt(d)
    return mamba_leaves(cfg, (groups, every - 1), ("mamba",)) + [
        (("shared", "ln1", "scale"), (d,), ("zeros",)),
        (("shared", "attn", "wq"), (d, d), ("normal", s)),
        (("shared", "attn", "wk"), (d, d), ("normal", s)),
        (("shared", "attn", "wv"), (d, d), ("normal", s)),
        (("shared", "attn", "wo"), (d, d), ("normal", s)),
        (("shared", "ln2", "scale"), (d,), ("zeros",)),
        (("shared", "mlp", "w_gate"), (d, ff), ("normal", s)),
        (("shared", "mlp", "w_up"), (d, ff), ("normal", s)),
        (("shared", "mlp", "w_down"), (ff, d), ("normal", 1 / math.sqrt(ff))),
        (("embed", "table"), (V, d), ("normal", 0.02)),
        (("final_norm", "scale"), (d,), ("zeros",)),
        (("lm_head", "w"), (d, V), ("normal", 0.02)),
    ]


def loss(params, tokens, labels, cfg: dict, num: Numerics):
    sp = params["shared"]

    @jax.checkpoint
    def shared_block(h):
        h = h + causal_attention(sp["attn"], rms_norm(h, sp["ln1"]["scale"]),
                                 cfg, num)
        return h + swiglu(sp["mlp"], rms_norm(h, sp["ln2"]["scale"]), num)

    def group(h, group_params):
        h, _ = jax.lax.scan(mamba_layer(cfg, num), h, group_params)
        return shared_block(h), None

    h = params["embed"]["table"][tokens]
    h, _ = jax.lax.scan(group, h, params["mamba"])
    h = rms_norm(h, params["final_norm"]["scale"])
    logits = num.mm("bld,dv->blv", h, params["lm_head"]["w"])
    return cross_entropy(logits, labels)
