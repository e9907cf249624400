"""A cell at a size the CPU runs in seconds, for the tests."""
from __future__ import annotations

import json

from bench.spec import BENCH_DIR, Cell, load_json

MAMBA = {"family": "ssm", "n_layers": 2, "d_model": 64, "n_heads": 0,
         "n_kv_heads": 0, "d_ff": 0, "vocab_size": 500, "ssm_state": 16,
         "ssm_head_dim": 16, "ssm_expand": 2, "ssm_conv_width": 4,
         "ssm_chunk": 32, "tie_embeddings": True, "dtype": "bfloat16"}
HYBRID = {"family": "hybrid", "n_layers": 4, "d_model": 64, "n_heads": 4,
          "n_kv_heads": 4, "d_ff": 128, "vocab_size": 500, "ssm_state": 16,
          "ssm_head_dim": 16, "ssm_expand": 2, "ssm_conv_width": 4,
          "ssm_chunk": 32, "shared_attn_every": 2, "mlp_type": "swiglu",
          "rope_theta": 10000.0, "dtype": "bfloat16"}


def tiny_cell(model=MAMBA, traffic="star4.b4x512", limits=None,
              dtype=None, **traffic_overrides) -> Cell:
    model = dict(model, **({"dtype": dtype} if dtype else {}))
    tr = dict(load_json(BENCH_DIR / "traffic" / f"{traffic}.json"),
              batch=2, seq_len=64, **traffic_overrides)
    config = {"name": "tiny", "source": "test", "model": model,
              "vocab_pad_multiple": 256}
    return Cell(name="tiny", chips=1, config=config, traffic=tr,
                limits=limits or {"loss_gap": 1e9, "grad_norm_gap": 1e9,
                                  "change_gap": 1e9},
                end_to_end=[{"name": n, "unit": "x"} for n in
                            ("tokens_per_s", "peak_hbm_gib", "setup_s")],
                per_layer=[])
