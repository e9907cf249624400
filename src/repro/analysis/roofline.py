"""Three-term roofline from dry-run artifacts (DESIGN.md §6).

    t_compute    = HLO_FLOPs   / (chips * peak_FLOP/s)
    t_memory     = HLO_bytes   / (chips * HBM_bw)
    t_collective = coll_bytes  / (chips * ICI link bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``; NOTE these are
*global* (all-device) totals when XLA reports the partitioned module, so we
detect per-device vs global by convention: jax reports cost for the
per-device executable — we therefore multiply by ``chips`` is NOT needed on
the numerator; both conventions normalise out as long as numerator and
denominator agree.  We treat cost_analysis output as per-device (matching the
post-partitioning module jax compiles) and collective bytes from the
partitioned HLO as per-device too.
"""
from __future__ import annotations

from repro.configs.base import INPUT_SHAPES, ModelConfig
from repro.launch.mesh import HW


def roofline_terms(
    flops: float,
    hbm_bytes: float,
    coll_bytes: float,
    *,
    per_device: bool = True,
    chips: int = 256,
) -> dict:
    """All inputs per-device when per_device=True, else global totals."""
    scale = 1.0 if per_device else 1.0 / chips
    t_compute = flops * scale / HW["peak_flops_bf16"]
    t_memory = hbm_bytes * scale / HW["hbm_bandwidth"]
    t_coll = coll_bytes * scale / HW["ici_bandwidth"]
    terms = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
    }
    dom = max(terms, key=terms.get)
    bound = max(t_compute, t_memory, t_coll)
    terms["dominant"] = dom.replace("t_", "").replace("_s", "")
    terms["step_lower_bound_s"] = bound
    # fraction of the bound spent doing useful math
    terms["compute_fraction"] = t_compute / bound if bound > 0 else 0.0
    return terms


def fused_sweep_traffic(d: int, S: int, C: int, *,
                        dtype_bytes: int = 4) -> dict:
    """HBM-traffic / FLOP model for the sweep-major fused DEPOSITUM update.

    The fused Pallas kernel reads {x, y, nu} and writes {x', nu'} exactly
    once per element — 5 array sweeps over the whole (S, C, d) grid.  The
    unfused jnp sequence materialises the momentum and the prox argument
    between HLOs: read {y, nu} write nu' (3 sweeps), read {x, nu'} write
    the shifted point (3), read it back and write x' (2) — 8 sweeps.
    FLOPs per element: 3 (momentum axpy) + 2 (prox shift) + ~4 (soft
    threshold select chain) = 9; the kernel is memory-bound by two orders
    of magnitude, so the ratio of sweeps IS the predicted speedup.

    The kernel views each leaf in place (no padding copy), so it moves
    exactly the logical ``d`` elements per client.
    """
    n = float(S) * C * d
    fused_bytes = 5.0 * n * dtype_bytes
    unfused_bytes = 8.0 * n * dtype_bytes
    flops = 9.0 * n
    return {
        "elements": n,
        "fused_bytes": fused_bytes,
        "unfused_bytes": unfused_bytes,
        "hbm_sweep_ratio": unfused_bytes / fused_bytes,
        "flops": flops,
        "arithmetic_intensity": flops / fused_bytes,
    }


def fused_sweep_roofline(traffic: dict, measured_s: float) -> dict:
    """Achieved-vs-roofline for one measured fused-sweep kernel wall time.

    Meaningful on TPU (Mosaic); on CPU interpret mode the fraction only
    documents how far the interpreter is from the HW model.
    """
    bw = HW["hbm_bandwidth"]
    t_mem = traffic["fused_bytes"] / bw
    achieved = traffic["fused_bytes"] / measured_s if measured_s > 0 else 0.0
    return {
        "roofline_t_memory_s": t_mem,
        "achieved_gbps": achieved / 1e9,
        "roofline_fraction": achieved / bw,
    }


def model_flops(cfg: ModelConfig, shape_name: str, n_clients: int = 1) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N_active*D (inference) global."""
    seq, global_batch, kind = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = seq * global_batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * global_batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * global_batch
