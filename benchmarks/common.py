"""Shared harness for the paper-validation benchmarks (Figs. 3-7, Table III).

Small models (linear / MLP — paper Sec. V-A) on synthetic classification
data with Dirichlet label skew, trained with DEPOSITUM or the FCO baselines.
Each experiment returns per-round metric curves as plain dicts, which run.py
summarises as CSV.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DepositumConfig,
    MixPlan,
    init as dep_init,
    local_then_comm_round,
    mixing_matrix,
    plan_spectral_lambda,
    spectral_lambda,
    stack_hypers,
    stack_mixplans,
    stationarity_metrics,
)
from repro.core.schedule import MixSchedule
from repro.data import make_classification
from repro.obs.metrics import round_values
from repro.training.backends import ExecutionBackend
from repro.training.sweep import sweep_run


# ---------------------------------------------------------------------------
# Paper-scale models on labelled vectors
# ---------------------------------------------------------------------------

def init_linear(key, d_in, n_classes):
    return {"w": jax.random.normal(key, (d_in, n_classes)) * 0.01,
            "b": jnp.zeros((n_classes,))}


def apply_linear(p, x):
    return x @ p["w"] + p["b"]


def init_mlp(key, d_in, n_classes, hidden=(64, 32)):
    keys = jax.random.split(key, len(hidden) + 1)
    dims = (d_in,) + tuple(hidden) + (n_classes,)
    return {
        f"l{i}": {
            "w": jax.random.normal(keys[i], (dims[i], dims[i + 1]))
            * (2.0 / dims[i]) ** 0.5,
            "b": jnp.zeros((dims[i + 1],)),
        }
        for i in range(len(dims) - 1)
    }


def apply_mlp(p, x):
    n = len(p)
    for i in range(n):
        x = x @ p[f"l{i}"]["w"] + p[f"l{i}"]["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


MODELS = {"linear": (init_linear, apply_linear),
          "mlp": (init_mlp, apply_mlp)}


def ce_loss(apply_fn, params, batch):
    x, y = batch["x"], batch["y"]
    logits = apply_fn(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@dataclasses.dataclass
class ExperimentConfig:
    model: str = "linear"
    n_clients: int = 10
    topology: str = "ring"
    theta: float = np.inf            # Dirichlet concentration (inf = IID)
    rounds: int = 60
    batch: int = 32
    n_features: int = 123            # A9A-like
    n_classes: int = 2
    n_samples: int = 4096
    seed: int = 0
    depositum: DepositumConfig = dataclasses.field(
        default_factory=lambda: DepositumConfig(
            alpha=0.1, beta=1.0, gamma=0.5, comm_period=5,
            prox_name="l1", prox_kwargs={"lam": 1e-4})
    )


def run_depositum(cfg: ExperimentConfig, collect_metrics: bool = True,
                  metrics_every: int | None = None, telemetry=None,
                  log_every: int = 1):
    """Returns dict of curves: loss, accuracy, stationarity terms, wall_s.

    Sequential (one-config) path: a fresh ``jit`` per config with the
    hyperparameters baked in — the pre-sweep-engine behaviour, kept as the
    ``--sequential`` fallback and as the wall-clock baseline.
    ``metrics_every=1`` evaluates metrics every round (matching the sweep
    engine's per-round metric cadence for fair timing comparisons).

    ``telemetry`` (a :class:`repro.obs.Telemetry`) additionally records the
    *in-loop* theory streams on-device every ``log_every`` rounds (no host
    sync; the exact-gradient eval metrics above keep their own cadence) and
    merges them into the curves as ``recorded_<name>`` lists.
    """
    ds = make_classification(
        n_samples=cfg.n_samples, n_features=cfg.n_features,
        n_classes=cfg.n_classes, n_clients=cfg.n_clients,
        theta=cfg.theta, seed=cfg.seed,
    )
    init_fn, apply_fn = MODELS[cfg.model]
    key = jax.random.PRNGKey(cfg.seed)
    params0 = init_fn(key, cfg.n_features, cfg.n_classes)

    loss_one = functools.partial(ce_loss, apply_fn)
    grad_one = jax.grad(loss_one)

    def grad_fn(x_stacked, batch):
        return jax.vmap(grad_one)(x_stacked, batch), {}

    # full-data tensors for metrics (global/local exact gradients)
    xs_full = jnp.asarray(np.stack([ds.client_arrays(i)[0]
                                    for i in range(cfg.n_clients)]))
    ys_full = jnp.asarray(np.stack([ds.client_arrays(i)[1]
                                    for i in range(cfg.n_clients)]))
    all_x = xs_full.reshape(-1, cfg.n_features)
    all_y = ys_full.reshape(-1)

    def local_at(xst):
        return jax.vmap(grad_one)(xst, {"x": xs_full, "y": ys_full})

    def global_at(xst):
        return jax.vmap(lambda p: grad_one(p, {"x": all_x, "y": all_y}))(xst)

    grad_fns = {"local_at": jax.jit(local_at), "global_at": jax.jit(global_at)}

    W = mixing_matrix(cfg.topology, cfg.n_clients)
    mixer = MixPlan.dense(W)
    dep = cfg.depositum
    state = dep_init(params0, cfg.n_clients)
    rnd = jax.jit(functools.partial(local_then_comm_round, grad_fn=grad_fn,
                                    config=dep, mixer=mixer))
    metrics_fn = jax.jit(functools.partial(stationarity_metrics,
                                           grad_fns=grad_fns, config=dep))

    record_fn = None
    carry = None
    if telemetry is not None:
        # the recorder reads the post-round state in its own jitted step, so
        # the round program (and the trajectory) is exactly the metrics-off
        # one; log_every rides as a traced operand
        sched = MixSchedule.constant(MixPlan.dense(jnp.asarray(W)))

        @jax.jit
        def record_fn(state, carry, log_every_op):
            vals = round_values(state, dep, mixer=sched, n=cfg.n_clients)
            r = (state.t - 1) // dep.comm_period
            return telemetry.record_and_emit(carry, vals, r, log_every_op)

        carry = telemetry.init_carry()

    rng = np.random.default_rng(cfg.seed + 7)
    curves: dict[str, list] = {k: [] for k in
                               ("round", "loss", "accuracy", "prox_grad_sq",
                                "consensus_x", "consensus_y", "consensus_nu",
                                "grad_est_err", "stationarity")}
    every = metrics_every if metrics_every else max(cfg.rounds // 20, 1)
    t0 = time.perf_counter()
    for r in range(cfg.rounds):
        bx, by = ds.stacked_batches(rng, cfg.batch, dep.comm_period)
        state, _ = rnd(state, batches={"x": jnp.asarray(bx),
                                       "y": jnp.asarray(by)})
        if record_fn is not None:
            carry = record_fn(state, carry, log_every)
        if collect_metrics and (r % every == 0 or r == cfg.rounds - 1):
            m = metrics_fn(state)
            pbar = jax.tree_util.tree_map(lambda v: jnp.mean(v, 0), state.x)
            logits = apply_fn(pbar, all_x)
            acc = float(jnp.mean(jnp.argmax(logits, -1) == all_y))
            curves["round"].append(r + 1)
            curves["loss"].append(float(loss_one(pbar, {"x": all_x,
                                                        "y": all_y})))
            curves["accuracy"].append(acc)
            for k in ("prox_grad_sq", "consensus_x", "consensus_y",
                      "consensus_nu", "grad_est_err", "stationarity"):
                curves[k].append(float(m[k]))
    curves["wall_s"] = time.perf_counter() - t0
    curves["iters"] = cfg.rounds * dep.comm_period
    curves["spectral_lambda"] = float(spectral_lambda(W))
    if telemetry is not None:
        telemetry.sync()
        sink = telemetry.memory_sink
        if sink is not None:
            curves["recorded_round"] = sink.rounds(0)
            for name in telemetry.spec.names:
                curves[f"recorded_{name}"] = sink.stream(name, 0)
    return curves


# ---------------------------------------------------------------------------
# Sweep-engine path: a whole hyperparameter grid as one compiled program
# ---------------------------------------------------------------------------

def _static_key(cfg: ExperimentConfig):
    """Everything that changes the traced program (grouping key).

    Topology is deliberately NOT part of the key: mixing is a traced
    ``MixPlan`` operand (dense W), so configs differing only in their graph
    stack on the same sweep axis as configs differing in step sizes.
    """
    d = cfg.depositum
    return (cfg.model, cfg.n_clients, cfg.theta, cfg.rounds,
            cfg.batch, cfg.n_features, cfg.n_classes, cfg.n_samples, cfg.seed,
            d.momentum, d.comm_period, d.prox_name, d.use_fused_kernel)


def _run_sweep_group(cfgs: list[ExperimentConfig], group_id: int,
                     collect_metrics: bool = True,
                     backend: ExecutionBackend | None = None,
                     telemetry=None, log_every: int = 1) -> list[dict]:
    """Run one static-config group through the sweep engine.

    Configs may differ in hyperparameters AND topology: both are traced
    operands (stacked Hyper axis + stacked dense-W MixPlan axis), so the
    group still compiles to one program.

    ``telemetry`` records the in-loop theory streams per config inside the
    compiled scan (``config`` tags follow group order); each returned row
    gains ``recorded_<name>`` lists from its config's event stream.
    """
    cfg = cfgs[0]
    dep = cfg.depositum
    ds = make_classification(
        n_samples=cfg.n_samples, n_features=cfg.n_features,
        n_classes=cfg.n_classes, n_clients=cfg.n_clients,
        theta=cfg.theta, seed=cfg.seed,
    )
    init_fn, apply_fn = MODELS[cfg.model]
    key = jax.random.PRNGKey(cfg.seed)
    params0 = init_fn(key, cfg.n_features, cfg.n_classes)

    loss_one = functools.partial(ce_loss, apply_fn)
    grad_one = jax.grad(loss_one)

    def grad_fn(x_stacked, batch):
        return jax.vmap(grad_one)(x_stacked, batch), {}

    xs_full = jnp.asarray(np.stack([ds.client_arrays(i)[0]
                                    for i in range(cfg.n_clients)]))
    ys_full = jnp.asarray(np.stack([ds.client_arrays(i)[1]
                                    for i in range(cfg.n_clients)]))
    all_x = xs_full.reshape(-1, cfg.n_features)
    all_y = ys_full.reshape(-1)

    grad_fns = {
        "local_at": lambda xst: jax.vmap(grad_one)(
            xst, {"x": xs_full, "y": ys_full}),
        "global_at": lambda xst: jax.vmap(
            lambda p: grad_one(p, {"x": all_x, "y": all_y}))(xst),
    }

    plans = [MixPlan.from_topology(c.topology, c.n_clients) for c in cfgs]
    if len({c.topology for c in cfgs}) == 1:
        plan = plans[0]          # shared graph: broadcast, no stacked W
    else:
        plan = stack_mixplans(plans)  # topology sweep axis: W is (S, n, n)
    lambdas = plan_spectral_lambda(plan, cfg.n_clients)
    hypers = stack_hypers([c.depositum.hyper() for c in cfgs])

    # pre-sample every round's minibatches with the sequential path's rng
    # stream, so sweep and sequential runs see identical data
    rng = np.random.default_rng(cfg.seed + 7)
    draws = [ds.stacked_batches(rng, cfg.batch, dep.comm_period)
             for _ in range(cfg.rounds)]
    batches = {"x": jnp.asarray(np.stack([d[0] for d in draws])),
               "y": jnp.asarray(np.stack([d[1] for d in draws]))}

    def metrics_fn(state, hyper):
        m = stationarity_metrics(state, grad_fns, dep, hyper=hyper)
        pbar = jax.tree_util.tree_map(lambda v: jnp.mean(v, 0), state.x)
        logits = apply_fn(pbar, all_x)
        m["accuracy"] = jnp.mean(
            (jnp.argmax(logits, -1) == all_y).astype(jnp.float32))
        m["loss"] = loss_one(pbar, {"x": all_x, "y": all_y})
        return m

    t0 = time.perf_counter()
    _final, outs = sweep_run(
        params0, grad_fn, dep, plan, hypers, batches,
        n_clients=cfg.n_clients,
        metrics_fn=metrics_fn if collect_metrics else None,
        backend=backend, telemetry=telemetry, log_every=log_every,
    )
    if collect_metrics:
        outs = jax.tree_util.tree_map(np.asarray, outs)  # block + to host
    else:
        jax.block_until_ready(_final)
    wall = time.perf_counter() - t0

    keys = ("loss", "accuracy", "prox_grad_sq", "consensus_x", "consensus_y",
            "consensus_nu", "grad_est_err", "stationarity")
    rows = []
    for s in range(len(cfgs)):
        curves: dict = {"round": list(range(1, cfg.rounds + 1))}
        for k in keys:
            curves[k] = ([float(v) for v in outs[k][s]]
                         if collect_metrics else [])
        curves["wall_s"] = wall / len(cfgs)
        curves["iters"] = cfg.rounds * dep.comm_period
        curves["spectral_lambda"] = float(np.atleast_1d(lambdas)[
            s if plan.is_stacked else 0])
        curves["sweep_group_id"] = group_id
        curves["sweep_group_size"] = len(cfgs)
        curves["sweep_group_wall_s"] = wall
        rows.append(curves)
    if telemetry is not None:
        telemetry.sync()
        sink = telemetry.memory_sink
        if sink is not None:
            for s, curves in enumerate(rows):
                curves["recorded_round"] = sink.rounds(s)
                for name in telemetry.spec.names:
                    curves[f"recorded_{name}"] = sink.stream(name, s)
    return rows


def run_depositum_grid(cfgs: list[ExperimentConfig],
                       collect_metrics: bool = True,
                       backend: ExecutionBackend | None = None,
                       telemetry=None, log_every: int = 1) -> list[dict]:
    """Run a grid of experiments through the sweep engine.

    Configs are grouped by static structure (model/shape/momentum kind/prox
    family/T0/...); each group becomes **one** compiled program that vmaps
    the whole federated run over the group's stacked Hyper axis — and, since
    mixing is a MixPlan operand, over a stacked dense-W topology axis too
    (topology is not a grouping key).  Per-row ``spectral_lambda`` reports
    each point's lambda = ||W - J||.  Returns per-config curve dicts in
    input order, shaped like :func:`run_depositum`'s output.  ``backend``
    selects where sweep points execute (default stacked-vmap).
    """
    groups: dict[tuple, list[int]] = {}
    for i, cfg in enumerate(cfgs):
        groups.setdefault(_static_key(cfg), []).append(i)
    if telemetry is not None and len(groups) > 1:
        # config tags are per compiled program; one recorder cannot keep
        # two groups' streams apart
        raise ValueError(
            f"telemetry needs a single static-config group, got "
            f"{len(groups)}; run groups separately with fresh recorders")

    out: list[dict | None] = [None] * len(cfgs)
    for gid, idxs in enumerate(groups.values()):
        rows = _run_sweep_group([cfgs[i] for i in idxs], gid, collect_metrics,
                                backend=backend, telemetry=telemetry,
                                log_every=log_every)
        for i, row in zip(idxs, rows):
            out[i] = row
    return out


def grid_wall_s(rows: list[dict]) -> float:
    """Total wall time of grid rows (counts each sweep group once)."""
    seen, total = set(), 0.0
    for r in rows:
        gid = r.get("sweep_group_id")
        if gid is None:
            total += r["wall_s"]
        elif gid not in seen:
            seen.add(gid)
            total += r["sweep_group_wall_s"]
    return total
