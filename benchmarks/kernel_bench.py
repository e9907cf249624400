"""Kernel micro-benchmarks: Pallas (interpret on CPU / Mosaic on TPU) vs the
pure-jnp reference path.  On CPU the numbers characterise the *reference*
path; the Pallas timings become meaningful on real TPU hardware.

Timing contract: every row reports the **blocked** per-iteration wall time
(``jax.block_until_ready`` inside the loop).  The old scheme — issue all
iterations and block once at the end — measured little more than dispatch
overhead on an async backend and deflated per-iter times; that number is
still reported separately as ``dispatch_us`` so queueing cost stays visible.

``fused_sweep_section`` benchmarks the sweep-major fused DEPOSITUM update
(grid (S, C, tiles), SMEM params table) against the vmapped jnp reference
and scores it against the HBM roofline model
(:mod:`repro.analysis.roofline`); ``benchmarks/run.py`` merges the result
into ``BENCH_sweep.json`` under ``kernel_fused_sweep``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.trace import Timing, time_fn
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.prox.kernel import (
    fused_update_pallas,
    fused_update_sweep_pallas,
    prox_pallas,
    sweep_params_table,
)
from repro.kernels.prox.ref import fused_update_ref, prox_l1_ref


# Timing / the blocked-vs-dispatch measurement now live in
# repro.obs.trace (time_fn); re-exported here for back-compat.
_time = time_fn


def fused_sweep_section(quick: bool = True) -> dict:
    """Benchmark the sweep-major fused update vs the vmapped jnp reference.

    Returns the ``kernel_fused_sweep`` dict for BENCH_sweep.json: measured
    blocked/dispatch times, the model HBM-sweep ratio (unfused/fused bytes),
    and the achieved-vs-roofline fraction for the fused kernel.
    """
    from repro.analysis.roofline import (fused_sweep_roofline,
                                         fused_sweep_traffic)

    S, C, d = (3, 4, 2048) if quick else (8, 8, 1 << 14)
    iters = 5 if quick else 20
    key = jax.random.PRNGKey(0)
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i),
                                     (S, C, d), jnp.float32) * 0.01
    x, y, nu = mk(0), mk(1), mk(2)
    alphas = jnp.linspace(0.05, 0.15, S)
    params = sweep_params_table(lam=1e-3, theta=4.0, alpha=alphas, gamma=0.8)

    fused = jax.jit(lambda a, b, c, p:
                    fused_update_sweep_pallas(a, b, c, p, kind="l1"))

    def one(xs, ys, nus, row):
        return fused_update_ref(xs, ys, nus, row[0], row[2], row[3],
                                prox_kind="l1", theta=row[1])

    unfused = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 0)))

    tf = _time(fused, x, y, nu, params, iters=iters)
    tu = _time(unfused, x, y, nu, params, iters=iters)

    traffic = fused_sweep_traffic(d, S, C)
    roof = fused_sweep_roofline(traffic, tf.blocked_us * 1e-6)
    return {
        "grid": "sweep-major fused update (S, C, param tiles)",
        "S": S, "C": C, "d": d,
        "backend": jax.default_backend(),
        "fused_us_blocked": round(tf.blocked_us, 1),
        "fused_us_dispatch": round(tf.dispatch_us, 1),
        "unfused_us_blocked": round(tu.blocked_us, 1),
        "unfused_us_dispatch": round(tu.dispatch_us, 1),
        "speedup_measured": round(tu.blocked_us / max(tf.blocked_us, 1e-9),
                                  3),
        "hbm_sweep_ratio_model": round(traffic["hbm_sweep_ratio"], 3),
        "model_bytes_fused": traffic["fused_bytes"],
        "model_bytes_unfused": traffic["unfused_bytes"],
        "model_flops": traffic["flops"],
        "achieved_gbps": round(roof["achieved_gbps"], 3),
        "roofline_fraction": round(roof["roofline_fraction"], 6),
        "quick": bool(quick),
    }


def run(quick: bool = False):
    key = jax.random.PRNGKey(0)
    rows = []
    on_tpu = jax.default_backend() == "tpu"

    n = 1 << 16 if quick else 1 << 20
    iters = 5 if quick else 20
    x = jax.random.normal(key, (n,)) * 0.01
    y = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.01
    nu = jax.random.normal(jax.random.fold_in(key, 2), (n,)) * 0.01

    def row(name, t: Timing, src):
        rows.append((name, t.blocked_us,
                     f"{src} (dispatch {t.dispatch_us:.1f}us)"))

    ref_prox = jax.jit(lambda v: prox_l1_ref(v, 1e-4, 0.1))
    row("prox_l1_ref", _time(ref_prox, x, iters=iters), "jnp oracle")
    if on_tpu:
        row("prox_l1_pallas",
            _time(lambda v: prox_pallas(v, kind="l1", lam=1e-4, alpha=0.1),
                  x, iters=iters), "pallas")

    ref_fused = jax.jit(lambda a, b, c: fused_update_ref(a, b, c, 1e-4, 0.1,
                                                         0.8))
    row("fused_update_ref", _time(ref_fused, x, y, nu, iters=iters),
        "jnp oracle")
    # unfused sequence for the fusion-win comparison
    unfused = jax.jit(lambda a, b, c: (
        prox_l1_ref(a - 0.1 * (0.8 * c + 0.2 * b), 1e-4, 0.1),
        0.8 * c + 0.2 * b))
    row("unfused_update", _time(unfused, x, y, nu, iters=iters),
        "jnp oracle")
    if on_tpu:
        row("fused_update_pallas",
            _time(lambda a, b, c: fused_update_pallas(
                a, b, c, kind="l1", lam=1e-4, alpha=0.1, gamma=0.8),
                x, y, nu, iters=iters), "pallas")

    B, L, H, KV, D = 1, 256 if quick else 1024, 8, 2, 128
    q = jax.random.normal(key, (B, L, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 3), (B, L, KV, D))
    v = jax.random.normal(jax.random.fold_in(key, 4), (B, L, KV, D))
    ref_attn = jax.jit(lambda a, b, c: attention_ref(a, b, c, causal=True))
    row("attention_ref", _time(ref_attn, q, k, v, iters=min(iters, 5)),
        "jnp oracle")
    if on_tpu:
        row("flash_attention",
            _time(lambda a, b, c: flash_attention(a, b, c, causal=True),
                  q, k, v, iters=min(iters, 5)), "pallas")
    return rows


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / few iters (CI mode)")
    cli = ap.parse_args()
    for name, us, src in run(quick=cli.quick):
        print(f"{name},{us:.1f},{src}")
    print(json.dumps({"kernel_fused_sweep": fused_sweep_section(cli.quick)},
                     indent=2))
