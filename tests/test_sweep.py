"""Sweep engine: a vmapped hyperparameter sweep must equal per-config
sequential runs leaf-for-leaf, and the Hyper operand path must equal the
classic config-floats path (tentpole equivalence guarantees)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DepositumConfig,
    Hyper,
    hyper_grid,
    init as dep_init,
    local_then_comm_round,
    mixing_matrix,
    n_sweep,
    stack_hypers,
    stationarity_metrics,
)
from repro.core import MixPlan, plan_spectral_lambda, stack_mixplans
from repro.training.sweep import (
    broadcast_batches,
    make_sweep_round,
    stack_rounds,
    sweep_init,
    sweep_run,
    sweep_run_fedalg,
    sweep_run_sequential,
)

N, D, T0, ROUNDS = 6, 12, 3, 8


def linear_problem(seed=0):
    """Least-squares clients: f_i(w) = 0.5||A_i w - b_i||^2 / m."""
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (N, 16, D))
    w_true = jax.random.normal(jax.random.fold_in(key, 1), (D,))
    b = jnp.einsum("nmd,d->nm", A, w_true)
    b = b + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), b.shape)

    def grad_fn(w_stacked, batch):
        # full-batch per-client gradients (deterministic => exact equality)
        r = jnp.einsum("nmd,nd->nm", A, w_stacked) - b
        return jnp.einsum("nmd,nm->nd", A, r) / A.shape[1], {}

    return grad_fn


def _grid_points(prox_name):
    lam0 = 1e-3
    return [
        dict(alpha=0.05, beta=1.0, gamma=0.5, lam=lam0),
        dict(alpha=0.1, beta=0.5, gamma=0.2, lam=5e-3),
        dict(alpha=0.02, beta=1.5, gamma=0.8, lam=1e-4),
    ]


@pytest.mark.parametrize("momentum", ["polyak", "nesterov"])
@pytest.mark.parametrize("prox_name", ["l1", "mcp"])
def test_sweep_matches_sequential(momentum, prox_name):
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum=momentum, comm_period=T0,
                          prox_name=prox_name,
                          prox_kwargs={"lam": 1e-3, "theta": 4.0}
                          if prox_name == "mcp" else {"lam": 1e-3})
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    hypers = stack_hypers([Hyper.create(**p, theta=4.0)
                           for p in _grid_points(prox_name)])
    batches = jnp.zeros((ROUNDS, T0, 1))

    def metrics_fn(state, hyper):
        return {"xsq": jnp.sum(state.x ** 2), "t": state.t}

    fs, outs = sweep_run(jnp.zeros(D), grad_fn, cfg, mixer, hypers, batches,
                         n_clients=N, metrics_fn=metrics_fn)
    fseq, outseq = sweep_run_sequential(jnp.zeros(D), grad_fn, cfg, mixer,
                                        hypers, batches, n_clients=N,
                                        metrics_fn=metrics_fn)
    for name in ("x", "y", "nu", "mu", "g"):
        np.testing.assert_allclose(
            np.asarray(getattr(fs, name)), np.asarray(getattr(fseq, name)),
            rtol=2e-5, atol=1e-6, err_msg=f"leaf {name}")
    np.testing.assert_allclose(np.asarray(outs["xsq"]),
                               np.asarray(outseq["xsq"]), rtol=2e-5,
                               atol=1e-6)


@pytest.mark.parametrize("momentum", ["polyak", "nesterov"])
@pytest.mark.parametrize("prox_name", ["l1", "mcp"])
def test_sweep_matches_classic_config_floats(momentum, prox_name):
    """Each sweep row == the pre-refactor path (floats baked into closures)."""
    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    points = _grid_points(prox_name)
    cfg0 = DepositumConfig(momentum=momentum, comm_period=T0,
                           prox_name=prox_name,
                           prox_kwargs={"lam": 1e-3, "theta": 4.0}
                           if prox_name == "mcp" else {"lam": 1e-3})
    hypers = stack_hypers([Hyper.create(**p, theta=4.0) for p in points])
    batches = jnp.zeros((ROUNDS, T0, 1))
    fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg0, mixer, hypers, batches,
                      n_clients=N)

    for s, p in enumerate(points):
        kwargs = {"lam": p["lam"]}
        if prox_name == "mcp":
            kwargs["theta"] = 4.0
        cfg = DepositumConfig(alpha=p["alpha"], beta=p["beta"],
                              gamma=p["gamma"], momentum=momentum,
                              comm_period=T0, prox_name=prox_name,
                              prox_kwargs=kwargs)
        state = dep_init(jnp.zeros(D), N)
        rnd = jax.jit(functools.partial(local_then_comm_round,
                                        grad_fn=grad_fn, config=cfg,
                                        mixer=mixer))
        for _ in range(ROUNDS):
            state, _ = rnd(state, batches=jnp.zeros((T0, 1)))
        np.testing.assert_allclose(np.asarray(fs.x[s]), np.asarray(state.x),
                                   rtol=2e-5, atol=1e-6)


def test_fused_kernel_sweep_matches_reference_sweep():
    """use_fused_kernel under the sweep vmap == unfused sweep (Polyak/l1)."""
    grad_fn = linear_problem()
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    hypers = hyper_grid(alpha=[0.02, 0.1], lam=[1e-4, 5e-3])
    hypers = hypers.replace(gamma=jnp.full_like(hypers.alpha, 0.6))
    batches = jnp.zeros((ROUNDS, T0, 1))
    out = {}
    for fused in (False, True):
        cfg = DepositumConfig(momentum="polyak", comm_period=T0,
                              prox_name="l1", prox_kwargs={"lam": 1e-4},
                              use_fused_kernel=fused)
        fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, mixer, hypers, batches,
                          n_clients=N)
        out[fused] = fs
    for name in ("x", "y", "nu", "g"):
        np.testing.assert_allclose(np.asarray(getattr(out[False], name)),
                                   np.asarray(getattr(out[True], name)),
                                   rtol=1e-5, atol=1e-6)


def test_streaming_round_and_batch_adapters():
    """make_sweep_round + broadcast_batches: streaming sweep loop works and
    the sweep dim broadcasts data without divergence across configs that
    share a hyper point."""
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
    hypers = stack_hypers([h, h])  # identical points must stay identical
    assert n_sweep(hypers) == 2

    states = sweep_init(jnp.zeros(D), N, 2)
    round_fn = make_sweep_round(grad_fn, cfg, mixer, batch_axis=0)
    for _ in range(4):
        b = broadcast_batches(jnp.zeros((T0, 1)), 2)
        states, _ = round_fn(states, hypers, b)
    np.testing.assert_allclose(np.asarray(states.x[0]),
                               np.asarray(states.x[1]), rtol=0, atol=0)
    assert int(states.t[0]) == 4 * T0


@pytest.mark.parametrize("alg", ["fedmid", "dsgd"])
def test_baseline_grid_vmaps_over_hyper(alg):
    """FCO baselines accept the same traced Hyper override, so their grids
    can ride one compiled program too (fair Table-III comparisons)."""
    from repro.core import mixing_matrix as mixmat
    from repro.core.fedopt import FedAlgConfig, make_algorithm

    grad_fn = linear_problem()
    cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name="l1",
                       prox_kwargs={"lam": 1e-3}, W=mixmat("ring", N))
    a = make_algorithm(alg, cfg)
    state0 = a.init(jnp.zeros(D), N)
    alphas = [0.02, 0.1, 0.3]
    hypers = stack_hypers([Hyper.create(alpha=al, lam=1e-3)
                           for al in alphas])
    batches = jnp.zeros((T0, 1))

    @jax.jit
    def swept(hypers):
        def one(hyper):
            st, _ = a.round(state0, batches, grad_fn, hyper=hyper)
            st, _ = a.round(st, batches, grad_fn, hyper=hyper)
            return st.x
        return jax.vmap(one)(hypers)

    got = swept(hypers)
    for s, al in enumerate(alphas):
        cfg_s = FedAlgConfig(alpha=al, local_steps=T0, prox_name="l1",
                             prox_kwargs={"lam": 1e-3}, W=mixmat("ring", N))
        a_s = make_algorithm(alg, cfg_s)
        st, _ = a_s.round(a_s.init(jnp.zeros(D), N), batches, grad_fn)
        st, _ = a_s.round(st, batches, grad_fn)
        np.testing.assert_allclose(np.asarray(got[s]), np.asarray(st.x),
                                   rtol=2e-5, atol=1e-6)


TOPOS = ["complete", "ring", "star", "torus"]


def test_topology_sweep_matches_sequential_and_classic():
    """A stacked dense-W MixPlan makes topology a sweep axis: one vmapped
    program over ≥3 graphs == per-topology sequential runs == the classic
    closure-mixer path (acceptance criterion of the MixPlan tentpole)."""
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    plans = stack_mixplans([MixPlan.from_topology(t, N) for t in TOPOS])
    h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
    hypers = stack_hypers([h] * len(TOPOS))
    batches = jnp.zeros((ROUNDS, T0, 1))

    fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, plans, hypers, batches,
                      n_clients=N)
    fseq, _ = sweep_run_sequential(jnp.zeros(D), grad_fn, cfg, plans, hypers,
                                   batches, n_clients=N)
    for name in ("x", "y", "nu", "mu", "g"):
        np.testing.assert_allclose(
            np.asarray(getattr(fs, name)), np.asarray(getattr(fseq, name)),
            rtol=2e-5, atol=1e-6, err_msg=f"leaf {name}")

    # each sweep point == the pre-refactor closure-mixer run of its graph
    for s, topo in enumerate(TOPOS):
        mixer = MixPlan.dense(mixing_matrix(topo, N))
        state = dep_init(jnp.zeros(D), N)
        rnd = jax.jit(functools.partial(local_then_comm_round,
                                        grad_fn=grad_fn, config=cfg,
                                        mixer=mixer, hyper=h))
        for _ in range(ROUNDS):
            state, _ = rnd(state, batches=jnp.zeros((T0, 1)))
        np.testing.assert_allclose(np.asarray(fs.x[s]), np.asarray(state.x),
                                   rtol=2e-5, atol=1e-6, err_msg=topo)

    # per-point spectral lambda is reportable from the same plan operand
    lams = plan_spectral_lambda(plans, N)
    assert lams.shape == (len(TOPOS),) and lams[0] < 1e-6 < lams[1] < 1.0


def test_topology_sweep_broadcasts_unstacked_hyper():
    """Topology-only sweeps need no stacked Hyper: the scalar hyper
    broadcasts over the plan axis."""
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    plans = stack_mixplans([MixPlan.from_topology(t, N)
                            for t in ("complete", "ring", "star")])
    h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
    batches = jnp.zeros((ROUNDS, T0, 1))
    fs, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, plans, h, batches,
                      n_clients=N)
    fs2, _ = sweep_run(jnp.zeros(D), grad_fn, cfg, plans, stack_hypers([h] * 3),
                       batches, n_clients=N)
    np.testing.assert_allclose(np.asarray(fs.x), np.asarray(fs2.x),
                               rtol=1e-6, atol=1e-7)


def test_zipped_hyper_and_topology_axes_must_agree():
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    plans = stack_mixplans([MixPlan.from_topology(t, N)
                            for t in ("complete", "ring")])
    hypers = stack_hypers([Hyper.create(lam=1e-3)] * 3)  # wrong length
    with pytest.raises(ValueError):
        sweep_run(jnp.zeros(D), grad_fn, cfg, plans, hypers,
                  jnp.zeros((ROUNDS, T0, 1)), n_clients=N)


def test_params_axis_sweeps_initialisations():
    """params_axis=0 batches per-seed initial points (Table III style)."""
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    plan = MixPlan.from_topology("ring", N)
    h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
    batches = jnp.zeros((ROUNDS, T0, 1))
    key = jax.random.PRNGKey(3)
    inits = jax.random.normal(key, (3, D)) * 0.1

    fs, _ = sweep_run(inits, grad_fn, cfg, plan, stack_hypers([h] * 3),
                      batches, n_clients=N, params_axis=0)
    for s in range(3):
        f1, _ = sweep_run(inits[s], grad_fn, cfg, plan, stack_hypers([h]),
                          batches, n_clients=N)
        np.testing.assert_allclose(np.asarray(fs.x[s]), np.asarray(f1.x[0]),
                                   rtol=2e-5, atol=1e-6)


def test_params_only_sweep_with_unstacked_hyper():
    """params_axis=0 with a scalar Hyper must broadcast the hyper over the
    seed axis — in BOTH the vmapped and the sequential engine (regression:
    the sequential path used to silently run only seed 0)."""
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    plan = MixPlan.from_topology("ring", N)
    h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
    batches = jnp.zeros((ROUNDS, T0, 1))
    inits = jax.random.normal(jax.random.PRNGKey(5), (3, D)) * 0.1

    fs, _ = sweep_run(inits, grad_fn, cfg, plan, h, batches,
                      n_clients=N, params_axis=0)
    fseq, _ = sweep_run_sequential(inits, grad_fn, cfg, plan, h, batches,
                                   n_clients=N, params_axis=0)
    assert fs.x.shape[0] == 3 and fseq.x.shape[0] == 3
    np.testing.assert_allclose(np.asarray(fs.x), np.asarray(fseq.x),
                               rtol=2e-5, atol=1e-6)
    # and the stacked runs differ across seeds (nothing collapsed to seed 0)
    assert float(jnp.max(jnp.abs(fs.x[0] - fs.x[1]))) > 1e-6


def test_fedalg_topology_sweep_with_unstacked_hyper():
    """sweep_run_fedalg must size the sweep from a stacked plan alone."""
    from repro.core import mixing_matrix as mixmat
    from repro.core.fedopt import FedAlgConfig, make_algorithm

    grad_fn = linear_problem()
    topos = ("complete", "ring", "star")
    cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name="l1",
                       prox_kwargs={"lam": 1e-3}, W=mixmat("ring", N))
    a = make_algorithm("dsgd", cfg)
    plans = stack_mixplans([MixPlan.from_topology(t, N) for t in topos])
    h = Hyper.create(alpha=0.1, lam=1e-3)
    batches = jnp.broadcast_to(jnp.zeros((T0, 1)), (ROUNDS, T0, 1))
    fs, _ = sweep_run_fedalg(a, jnp.zeros(D), grad_fn, h, batches,
                             n_clients=N, plan=plans)
    fs2, _ = sweep_run_fedalg(a, jnp.zeros(D), grad_fn,
                              stack_hypers([h] * len(topos)), batches,
                              n_clients=N, plan=plans)
    np.testing.assert_allclose(np.asarray(fs.x), np.asarray(fs2.x),
                               rtol=1e-6, atol=1e-7)


def test_fedalg_topology_sweep_through_engine():
    """DSGD rides the same engine: a stacked dense plan sweeps the baseline
    over topologies in one compiled program, matching per-plan rounds."""
    from repro.core import mixing_matrix as mixmat
    from repro.core.fedopt import FedAlgConfig, make_algorithm

    grad_fn = linear_problem()
    topos = ("complete", "ring", "star")
    cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name="l1",
                       prox_kwargs={"lam": 1e-3}, W=mixmat("ring", N))
    a = make_algorithm("dsgd", cfg)
    plans = stack_mixplans([MixPlan.from_topology(t, N) for t in topos])
    h = Hyper.create(alpha=0.1, lam=1e-3)
    hypers = stack_hypers([h] * len(topos))
    batches = jnp.broadcast_to(jnp.zeros((T0, 1)), (ROUNDS, T0, 1))

    fs, _ = sweep_run_fedalg(a, jnp.zeros(D), grad_fn, hypers, batches,
                             n_clients=N, plan=plans)
    for s, t in enumerate(topos):
        st = a.init(jnp.zeros(D), N)
        p = MixPlan.from_topology(t, N)
        for _ in range(ROUNDS):
            st, _ = a.round(st, jnp.zeros((T0, 1)), grad_fn, hyper=h, plan=p)
        np.testing.assert_allclose(np.asarray(fs.x[s]), np.asarray(st.x),
                                   rtol=2e-5, atol=1e-6, err_msg=t)


def test_server_algorithms_reject_topology_plan():
    from repro.core import mixing_matrix as mixmat
    from repro.core.fedopt import FedAlgConfig, make_algorithm

    grad_fn = linear_problem()
    cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name="l1",
                       prox_kwargs={"lam": 1e-3}, W=mixmat("ring", N))
    a = make_algorithm("fedmid", cfg)
    st = a.init(jnp.zeros(D), N)
    with pytest.raises(ValueError):
        a.round(st, jnp.zeros((T0, 1)), grad_fn,
                plan=MixPlan.from_topology("ring", N))


def test_make_sweep_round_plan_is_runtime_operand():
    """Swapping same-structure plans must NOT retrace the streaming round
    (regression: the plan used to be baked into the jit closure, violating
    the operand contract in training.backends — every new topology grid
    recompiled and stacked W leaves became program constants)."""
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    base = linear_problem()
    traces = []

    def grad_fn(x, batch):
        traces.append(1)  # trace-time side effect: counts compilations
        return base(x, batch)

    plans_a = stack_mixplans([MixPlan.from_topology("ring", N)] * 2)
    plans_b = stack_mixplans([MixPlan.from_topology("complete", N)] * 2)
    hypers = stack_hypers([Hyper.create(alpha=0.05, lam=1e-3)] * 2)
    states = sweep_init(jnp.zeros(D), N, 2)
    b = broadcast_batches(jnp.zeros((T0, 1)), 2)

    round_fn = make_sweep_round(grad_fn, cfg, plans_a, batch_axis=0)
    s_ring, _ = round_fn(states, hypers, b)
    one_trace = sum(traces)
    s_complete, _ = round_fn(states, hypers, b, plan=plans_b)
    assert sum(traces) == one_trace, (
        f"plan swap retraced ({sum(traces)} trace events after swap vs "
        f"{one_trace} for one compile)")
    # and the swapped plan is actually used, not a stale constant
    assert float(jnp.max(jnp.abs(s_ring.x - s_complete.x))) > 1e-8
    # the complete-graph round must equal running with that plan directly
    direct = make_sweep_round(base, cfg, plans_b, batch_axis=0)
    s_direct, _ = direct(states, hypers, b)
    np.testing.assert_allclose(np.asarray(s_complete.x),
                               np.asarray(s_direct.x), rtol=1e-6, atol=1e-7)


def test_make_sweep_round_accepts_unstacked_hyper():
    """A scalar Hyper must broadcast over the sweep axis exactly as in
    sweep_run (regression: hard-coded in_axes=0 crashed inside vmap)."""
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    plans = stack_mixplans([MixPlan.from_topology("ring", N),
                            MixPlan.from_topology("star", N)])
    h = Hyper.create(alpha=0.05, beta=1.0, gamma=0.5, lam=1e-3)
    states = sweep_init(jnp.zeros(D), N, 2)
    b = broadcast_batches(jnp.zeros((T0, 1)), 2)

    round_fn = make_sweep_round(grad_fn, cfg, plans, batch_axis=0)
    s_scalar, _ = round_fn(states, h, b)                 # used to raise
    s_stacked, _ = round_fn(states, stack_hypers([h, h]), b)
    np.testing.assert_allclose(np.asarray(s_scalar.x),
                               np.asarray(s_stacked.x), rtol=0, atol=0)


def test_fedalg_sweep_applies_mixing_gate():
    """sweep_run_fedalg must apply the same Assumption-2 legality gate as
    sweep_run (regression: an invalid W silently ran for baseline grids)."""
    from repro.core.fedopt import FedAlgConfig, make_algorithm

    grad_fn = linear_problem()
    cfg = FedAlgConfig(alpha=0.1, local_steps=T0, prox_name="l1",
                       prox_kwargs={"lam": 1e-3}, W=mixing_matrix("ring", N))
    a = make_algorithm("dsgd", cfg)
    bad = MixPlan.dense(jnp.eye(N) * 2.0)  # rows sum to 2: not stochastic
    with pytest.raises(ValueError):
        sweep_run_fedalg(a, jnp.zeros(D), grad_fn,
                         Hyper.create(alpha=0.1, lam=1e-3),
                         jnp.zeros((ROUNDS, T0, 1)), n_clients=N, plan=bad)
    # stacked grids are gated per point too
    good = MixPlan.from_topology("ring", N)
    with pytest.raises(ValueError):
        sweep_run_fedalg(a, jnp.zeros(D), grad_fn,
                         Hyper.create(alpha=0.1, lam=1e-3),
                         jnp.zeros((ROUNDS, T0, 1)), n_clients=N,
                         plan=stack_mixplans([good, bad]))


def test_stack_rounds_and_metrics_shapes():
    grad_fn = linear_problem()
    cfg = DepositumConfig(momentum="polyak", comm_period=T0, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    mixer = MixPlan.dense(mixing_matrix("ring", N))
    # base= anchors unswept fields (lam here) at the config's actual values
    hypers = hyper_grid(base=cfg.hyper(), alpha=[0.02, 0.05, 0.1])
    assert abs(float(hypers.lam[0]) - 1e-3) < 1e-9
    batches = stack_rounds([jnp.zeros((T0, 1)) for _ in range(ROUNDS)])
    assert batches.shape == (ROUNDS, T0, 1)

    grad_fns = {"local_at": lambda x: grad_fn(x, None)[0],
                "global_at": lambda x: grad_fn(x, None)[0]}

    def metrics_fn(state, hyper):
        return stationarity_metrics(state, grad_fns, cfg, hyper=hyper)

    fs, outs = sweep_run(jnp.zeros(D), grad_fn, cfg, mixer, hypers, batches,
                         n_clients=N, metrics_fn=metrics_fn)
    assert fs.x.shape == (3, N, D)
    assert outs["stationarity"].shape == (3, ROUNDS)
    assert np.all(np.isfinite(np.asarray(outs["stationarity"])))
