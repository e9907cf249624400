"""Composite-objective showcase: the same federated task under different
regularisers h — none / l1 / MCP / SCAD — comparing the sparsity-accuracy
trade-off (the reason nonconvex composite FL exists).

    PYTHONPATH=src python examples/composite_sparsity.py
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DepositumConfig,
    MixPlan,
    init,
    local_then_comm_round,
    mixing_matrix,
)
from repro.data import make_classification


def main():
    n, d, classes = 10, 200, 10
    # sparse teacher: only 25% of features matter
    ds = make_classification(n_samples=4096, n_features=d, n_classes=classes,
                             n_clients=n, theta=1.0, seed=1,
                             teacher_sparsity=0.75)

    def loss(w, batch):
        logits = batch["x"] @ w
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, batch["y"][..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    grad_one = jax.grad(loss)

    def grad_fn(w, batch):
        return jax.vmap(grad_one)(w, batch), {}

    W = mixing_matrix("ring", n)
    all_x = jnp.asarray(ds.x)
    all_y = jnp.asarray(ds.y)

    REGS = [
        ("none", "zero", {}),
        ("l1", "l1", {"lam": 8e-3}),
        ("mcp", "mcp", {"lam": 8e-3, "theta": 4.0}),
        ("scad", "scad", {"lam": 8e-3, "theta": 4.0}),
    ]
    print(f"{'h':8s} {'accuracy':>9s} {'sparsity':>9s} {'|w|_0':>7s}")
    for name, prox, kwargs in REGS:
        cfg = DepositumConfig(alpha=0.1, beta=1.0, gamma=0.5, comm_period=5,
                              prox_name=prox, prox_kwargs=kwargs)
        state = init(jnp.zeros((d, classes)), n)
        rnd = jax.jit(functools.partial(local_then_comm_round,
                                        grad_fn=grad_fn, config=cfg,
                                        mixer=MixPlan.dense(W)))
        rng = np.random.default_rng(0)
        for _ in range(80):
            bx, by = ds.stacked_batches(rng, 32, cfg.comm_period)
            state, _ = rnd(state, batches={"x": jnp.asarray(bx),
                                           "y": jnp.asarray(by)})
        # the stored x after a comm round is a *mixture* of prox outputs, so
        # exact zeros are blurred; the deployable sparse model is one final
        # prox step at the consensus point (standard prox-extraction)
        from repro.core.prox import get_prox
        wbar = jnp.mean(state.x, 0)
        nubar = jnp.mean(state.nu, 0)
        if prox != "zero":
            w_dep = get_prox(prox, **kwargs).prox(wbar - cfg.alpha * nubar,
                                                  cfg.alpha)
        else:
            w_dep = wbar
        acc = float(jnp.mean(jnp.argmax(all_x @ w_dep, -1) == all_y))
        zeros = float(jnp.mean(jnp.abs(w_dep) < 1e-8))
        nnz = int(jnp.sum(jnp.abs(w_dep) >= 1e-8))
        print(f"{name:8s} {acc:9.3f} {zeros:9.2%} {nnz:7d}")
    print("\nMCP/SCAD (weakly convex) keep accuracy at higher sparsity than "
          "l1 — the paper's motivation for going beyond convex h.")


if __name__ == "__main__":
    main()
