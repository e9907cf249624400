"""Training launcher: DEPOSITUM over a zoo model, one process.

Runs on whatever JAX finds: a TPU chip (the Pallas kernels lower through
Mosaic) or the CPU (kernels in interpret mode).  ``--arch`` picks any
registered architecture at its published widths; ``--reduced`` picks the
small variant that trains end-to-end on the CPU.  ``--fused require``
engages the fused Pallas update kernels and fails if they cannot serve a
step.  The persistent compile cache follows
:func:`repro.launch.compile_cache.enable_compile_cache`.

Example (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
        --clients 4 --rounds 20 --t0 4 --topology ring --prox l1 --lam 1e-5
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import DepositumConfig
from repro.data import make_federated_lm_streams
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.training import save_checkpoint
from repro.training.train_loop import (
    FederatedTrainer,
    TrainerConfig,
    lm_batch_iterator,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256,
                    help="sequence length; a multiple of the config's "
                         "ssm_chunk for state-space families")
    ap.add_argument("--t0", type=int, default=4, help="communication period T0")
    ap.add_argument("--alpha", type=float, default=0.02)
    ap.add_argument("--beta", type=float, default=1.0)
    ap.add_argument("--gamma", type=float, default=0.8)
    ap.add_argument("--momentum", default="polyak",
                    choices=["polyak", "nesterov", "none"])
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--prox", default="l1",
                    choices=["l1", "mcp", "scad", "l2sq", "zero"])
    ap.add_argument("--lam", type=float, default=1e-5)
    ap.add_argument("--fused", default="off",
                    choices=["auto", "require", "off"],
                    help="fused Pallas update kernels (DepositumConfig.fused)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.family in ("ssm", "hybrid") and args.seq % cfg.ssm_chunk:
        ap.error(f"--seq {args.seq} is not a multiple of {cfg.name}'s "
                 f"ssm_chunk={cfg.ssm_chunk}")
    enable_compile_cache()
    model = build_model(cfg)
    prox_kwargs = {"lam": args.lam}
    if args.prox in ("mcp", "scad"):
        prox_kwargs["theta"] = 4.0
    if args.prox == "zero":
        prox_kwargs = {}
    dep = DepositumConfig(
        alpha=args.alpha, beta=args.beta, gamma=args.gamma,
        momentum=args.momentum, comm_period=args.t0,
        prox_name=args.prox, prox_kwargs=prox_kwargs, fused=args.fused,
    )
    tc = TrainerConfig(n_clients=args.clients, topology=args.topology,
                       depositum=dep, seed=args.seed)
    trainer = FederatedTrainer(model, tc)
    from repro.core import plan_spectral_lambda
    print(f"topology {args.topology} on {args.clients} clients: "
          f"spectral lambda = {float(plan_spectral_lambda(trainer.plan, args.clients)):.4f}")
    state = trainer.init_state(jax.random.PRNGKey(args.seed))
    stream = make_federated_lm_streams(cfg.vocab_size, args.clients,
                                       seed=args.seed)
    it = lm_batch_iterator(stream, tc, batch=args.batch, seq_len=args.seq)

    t0 = time.time()
    state, history = trainer.run(state, it, args.rounds)
    for rec in history:
        print(json.dumps(rec))
    print(f"trained {args.rounds} rounds in {time.time()-t0:.1f}s "
          f"({args.rounds * args.t0} iterations)")

    if args.ckpt:
        save_checkpoint(args.ckpt, trainer.mean_params(state),
                        step=args.rounds)
        print("checkpoint ->", args.ckpt)
    if args.log:
        os.makedirs(os.path.dirname(os.path.abspath(args.log)), exist_ok=True)
        with open(args.log, "w") as f:
            json.dump(history, f, indent=2)


if __name__ == "__main__":
    main()
