"""Training FLOPs per token of the Zamba2-style hybrid, from shapes.

Mamba-2 layers as in ``ssm.py``; one shared transformer block per group of
``shared_attn_every`` layers: q, k, v, o projections 8 d^2, attention
scores and values 2 L d each (full L x L, as in the usual 6N + 12 l L d
count), SwiGLU 6 d ff; output head 2 d V.  Forward x 3 for training.
"""
from bench.flops.ssm import mamba_layer_forward, padded_vocab


def forward_per_token(cfg: dict, seq_len: int) -> float:
    d, ff = cfg["d_model"], cfg["d_ff"]
    every = cfg["shared_attn_every"]
    groups = cfg["n_layers"] // every
    shared = 8 * d * d + 4 * seq_len * d + 6 * d * ff
    return groups * ((every - 1) * mamba_layer_forward(cfg) + shared) + \
        2 * d * padded_vocab(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3 * forward_per_token(cfg, seq_len)
