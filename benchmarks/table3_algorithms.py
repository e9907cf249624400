"""Table III: DEPOSITUM (OPTION I/II) vs FedMiD / FedDR / FedADMM.

MLP on synthetic classification with SCAD regulariser, under IID / Dir(1) /
Dir(0.1) partitions; mean +/- std of test accuracy over 3 seeds.
DEPOSITUM runs on a complete graph, baselines emulate the star/server setup
(their aggregation is a client mean), mirroring the paper's setting.

Execution rides the sweep engine: for every (partition, algorithm) cell the
3 seeds — distinct datasets, initialisations, and minibatch streams — are
stacked on the sweep axis (``params_axis=0``, ``batch_axis=0``) and run as
**one** compiled program via ``sweep_run`` (DEPOSITUM) /
``sweep_run_fedalg`` (baselines), the same engine the DEPOSITUM figure
grids use.  ``run(sequential=True)`` restores the one-fresh-jit-per-run
legacy path (same data streams, same results).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DepositumConfig,
    Hyper,
    MixPlan,
    init as dep_init,
    local_then_comm_round,
    mixing_matrix,
    stack_hypers,
)
from repro.core.fedopt import FedAlgConfig, make_algorithm
from repro.data import make_classification
from repro.training.sweep import sweep_run, sweep_run_fedalg

from benchmarks.common import MODELS, ce_loss

PARTITIONS = {"IID": np.inf, "Dir(1)": 1.0, "Dir(0.1)": 0.1}
ALGS = ["depositum-I", "depositum-II", "fedmid", "feddr", "fedadmm"]
N_CLIENTS = 10
ROUNDS = 30
T0 = 5
SEEDS = (0, 1, 2)
PROX = ("scad", {"lam": 1e-4, "theta": 4.0})


def _test_accuracy(apply_fn, params, ds):
    # held-out evaluation: last 25% of samples (paper uses test split)
    cut = int(len(ds.y) * 0.75)
    logits = apply_fn(params, jnp.asarray(ds.x[cut:]))
    return float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(ds.y[cut:])))


def _seed_problem(theta: float, seed: int):
    """(dataset, params0, pre-sampled per-round batches) for one seed.

    The rng stream matches the legacy sequential path exactly (a fresh
    ``default_rng(seed + 13)`` drawing one T0-block per round), so batched
    and sequential runs see identical data.
    """
    ds = make_classification(n_samples=4096, n_features=64, n_classes=10,
                             n_clients=N_CLIENTS, theta=theta, seed=seed)
    init_fn, _ = MODELS["mlp"]
    params0 = init_fn(jax.random.PRNGKey(seed), 64, 10)
    rng = np.random.default_rng(seed + 13)
    draws = [ds.stacked_batches(rng, 32, T0) for _ in range(ROUNDS)]
    batches = {"x": jnp.asarray(np.stack([d[0] for d in draws])),
               "y": jnp.asarray(np.stack([d[1] for d in draws]))}
    return ds, params0, batches


def _grad_fn():
    _, apply_fn = MODELS["mlp"]
    loss_one = functools.partial(ce_loss, apply_fn)
    grad_one = jax.grad(loss_one)

    def grad_fn(xst, batch):
        return jax.vmap(grad_one)(xst, batch), {}

    return grad_fn


def _dep_config(alg: str) -> DepositumConfig:
    prox_name, prox_kwargs = PROX
    momentum = "polyak" if alg.endswith("-I") else "nesterov"
    return DepositumConfig(alpha=0.1, beta=1.0, gamma=0.5, momentum=momentum,
                           comm_period=T0, prox_name=prox_name,
                           prox_kwargs=prox_kwargs)


def run_cell(alg: str, theta: float) -> list[float]:
    """All seeds of one (algorithm, partition) cell as ONE compiled program."""
    _, apply_fn = MODELS["mlp"]
    grad_fn = _grad_fn()
    problems = [_seed_problem(theta, s) for s in SEEDS]
    dss = [p[0] for p in problems]
    params0 = jax.tree_util.tree_map(lambda *ps: jnp.stack(ps),
                                     *[p[1] for p in problems])
    batches = jax.tree_util.tree_map(lambda *bs: jnp.stack(bs),
                                     *[p[2] for p in problems])
    prox_name, prox_kwargs = PROX

    if alg.startswith("depositum"):
        dep = _dep_config(alg)
        hypers = stack_hypers([dep.hyper()] * len(SEEDS))
        plan = MixPlan.from_topology("complete", N_CLIENTS)
        final, _ = sweep_run(params0, grad_fn, dep, plan, hypers, batches,
                             n_clients=N_CLIENTS, params_axis=0, batch_axis=0)
    else:
        cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name=prox_name,
                           prox_kwargs=prox_kwargs, eta=0.5,
                           W=mixing_matrix("complete", N_CLIENTS))
        a = make_algorithm(alg, cfg)
        hypers = stack_hypers([Hyper.create(alpha=cfg.alpha,
                                            lam=prox_kwargs["lam"],
                                            theta=prox_kwargs["theta"])]
                              * len(SEEDS))
        final, _ = sweep_run_fedalg(a, params0, grad_fn, hypers, batches,
                                    n_clients=N_CLIENTS,
                                    params_axis=0, batch_axis=0)

    accs = []
    for i, ds in enumerate(dss):
        x_i = jax.tree_util.tree_map(lambda v: v[i], final.x)
        pbar = jax.tree_util.tree_map(lambda v: jnp.mean(v, 0), x_i)
        accs.append(_test_accuracy(apply_fn, pbar, ds))
    return accs


def run_one(alg: str, theta: float, seed: int) -> float:
    """Legacy sequential reference: one fresh-jit run for one seed."""
    _, apply_fn = MODELS["mlp"]
    grad_fn = _grad_fn()
    ds, params0, batches = _seed_problem(theta, seed)
    prox_name, prox_kwargs = PROX
    if alg.startswith("depositum"):
        dep = _dep_config(alg)
        W = mixing_matrix("complete", N_CLIENTS)
        state = dep_init(params0, N_CLIENTS)
        rnd = jax.jit(functools.partial(local_then_comm_round,
                                        grad_fn=grad_fn, config=dep,
                                        mixer=MixPlan.dense(W)))
        for r in range(ROUNDS):
            state, _ = rnd(state, batches=jax.tree_util.tree_map(
                lambda b: b[r], batches))
        pbar = jax.tree_util.tree_map(lambda v: jnp.mean(v, 0), state.x)
    else:
        cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name=prox_name,
                           prox_kwargs=prox_kwargs, eta=0.5,
                           W=mixing_matrix("complete", N_CLIENTS))
        a = make_algorithm(alg, cfg)
        st = a.init(params0, N_CLIENTS)
        for r in range(ROUNDS):
            st, _ = a.round(st, jax.tree_util.tree_map(lambda b: b[r],
                                                       batches), grad_fn)
        pbar = jax.tree_util.tree_map(lambda v: jnp.mean(v, 0), st.x)
    return _test_accuracy(apply_fn, pbar, ds)


def run(sequential: bool = False):
    rows = []
    for part_name, theta in PARTITIONS.items():
        if sequential:
            accs = {alg: [run_one(alg, theta, s) for s in SEEDS]
                    for alg in ALGS}
        else:
            accs = {alg: run_cell(alg, theta) for alg in ALGS}
        row = {"partition": part_name}
        for alg in ALGS:
            row[alg] = f"{np.mean(accs[alg]):.4f}±{np.std(accs[alg]):.4f}"
            row[f"_{alg}_mean"] = float(np.mean(accs[alg]))
        rows.append(row)
    return rows


def check(rows) -> dict:
    """Paper claim: DEPOSITUM best-in-row (we assert >= max(baselines)-eps)."""
    ok = True
    for row in rows:
        dep = max(row["_depositum-I_mean"], row["_depositum-II_mean"])
        base = max(row[f"_{a}_mean"] for a in ("fedmid", "feddr", "fedadmm"))
        ok = ok and (dep >= base - 0.02)
    return {"depositum_best_or_tied": ok}


if __name__ == "__main__":
    rows = run()
    for r in rows:
        print({k: v for k, v in r.items() if not k.startswith("_")})
    print(check(rows))
