"""Quickstart: DEPOSITUM on a 10-client ring solving sparse logistic
regression (the paper's A9A-style setting), in ~30 seconds on CPU.

    PYTHONPATH=src python examples/quickstart.py
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
    stationarity_metrics,
)
from repro.data import make_classification


def main():
    n_clients, d, n_classes = 10, 123, 2
    ds = make_classification(n_samples=4096, n_features=d,
                             n_classes=n_classes, n_clients=n_clients,
                             theta=1.0, seed=0)

    def loss(w, batch):
        logits = batch["x"] @ w
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, batch["y"][..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    grad_one = jax.grad(loss)

    def grad_fn(w_stacked, batch):
        return jax.vmap(grad_one)(w_stacked, batch), {}

    # DEPOSITUM: Polyak momentum, T0=5 local steps per round, l1 prox
    cfg = DepositumConfig(alpha=0.1, beta=1.0, gamma=0.5, momentum="polyak",
                          comm_period=5, prox_name="l1",
                          prox_kwargs={"lam": 5e-3})
    W = mixing_matrix("ring", n_clients)
    state = init(jnp.zeros((d, n_classes)), n_clients)
    rnd = jax.jit(functools.partial(local_then_comm_round, grad_fn=grad_fn,
                                    config=cfg, mixer=MixPlan.dense(W)))

    xs = jnp.asarray(np.stack([ds.client_arrays(i)[0] for i in range(n_clients)]))
    ys = jnp.asarray(np.stack([ds.client_arrays(i)[1] for i in range(n_clients)]))
    grad_fns = {
        "local_at": lambda w: jax.vmap(grad_one)(w, {"x": xs, "y": ys}),
        "global_at": lambda w: jax.vmap(
            lambda p: grad_one(p, {"x": xs.reshape(-1, d),
                                   "y": ys.reshape(-1)}))(w),
    }

    rng = np.random.default_rng(0)
    for r in range(60):
        bx, by = ds.stacked_batches(rng, 32, cfg.comm_period)
        state, _ = rnd(state, batches={"x": jnp.asarray(bx),
                                       "y": jnp.asarray(by)})
        if (r + 1) % 20 == 0:
            m = stationarity_metrics(state, grad_fns, cfg)
            wbar = jnp.mean(state.x, 0)
            acc = float(jnp.mean(
                jnp.argmax(xs.reshape(-1, d) @ wbar, -1) == ys.reshape(-1)))
            sparsity = float(jnp.mean(jnp.abs(state.x[0]) < 1e-8))
            print(f"round {r+1:3d}  acc={acc:.3f}  sparsity={sparsity:.2f}  "
                  f"stationarity={float(m['stationarity']):.2e}  "
                  f"consensus={float(m['consensus_x']):.2e}")
    print("done — l1 prox produced a sparse consensus model on a ring of 10 "
          "clients, no server.")


if __name__ == "__main__":
    main()
