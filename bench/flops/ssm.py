"""Training FLOPs per token of the Mamba-2 language model, from shapes.

Counts every matrix product of the forward pass and doubles it for the
backward (3x in all); recomputation, the conv, norms, elementwise work and
the optimizer update are left out.  Per layer and token:

* in-projection 2 d (2 di + 2 N + H); out-projection 2 di d;
* the chunked SSD products, with chunk Q as the algorithm defines them
  (full Q x Q blocks): C B^T 2 Q N, scores x X 2 Q H P, chunk states
  2 H N P, state readout 2 H N P;

plus the output head 2 d V over the padded vocabulary.
"""


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def mamba_layer_forward(cfg: dict) -> float:
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    N, P, Q = cfg["ssm_state"], cfg["ssm_head_dim"], cfg["ssm_chunk"]
    H = di // P
    proj = 2 * d * (2 * di + 2 * N + H) + 2 * di * d
    ssd = 2 * Q * N + 2 * Q * H * P + 2 * H * N * P + 2 * H * N * P
    return proj + ssd


def forward_per_token(cfg: dict, seq_len: int) -> float:
    return cfg["n_layers"] * mamba_layer_forward(cfg) + \
        2 * cfg["d_model"] * padded_vocab(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3 * forward_per_token(cfg, seq_len)
