"""DEPOSITUM algorithm invariants and convergence (paper Secs. III-IV)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    DepositumConfig,
    init,
    local_then_comm_round,
    MixPlan,
    mixing_matrix,
    stationarity_metrics,
    step,
    identity_mixer,
)
from repro.core.depositum import consensus_error


def quadratic_problem(n=10, d=8, seed=0):
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (n, d, d))
    A = jnp.einsum("nij,nkj->nik", A, A) / d + 0.5 * jnp.eye(d)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, d))

    def grad_fn(x, batch):
        return jnp.einsum("nij,nj->ni", A, x) - b, {}

    Abar, bbar = jnp.mean(A, 0), jnp.mean(b, 0)
    grad_fns = {
        "local_at": lambda x: grad_fn(x, None)[0],
        "global_at": lambda x: jnp.einsum("ij,nj->ni", Abar, x) - bbar,
    }
    return grad_fn, grad_fns


def run_rounds(cfg, n, grad_fn, rounds, topology="ring", d=8, seed=0):
    W = mixing_matrix(topology, n)
    mixer = MixPlan.dense(W)
    state = init(jnp.zeros(d), n)
    rnd = jax.jit(functools.partial(
        local_then_comm_round, grad_fn=grad_fn, config=cfg, mixer=mixer
    ))
    batches = jnp.zeros((cfg.comm_period, 1))
    for _ in range(rounds):
        state, _ = rnd(state, batches=batches)
    return state


# ---------------------------------------------------------------------------
# Tracking invariant (Remark 1): J y^t = beta * J g^t for all t,
# under any interleaving of local and communication steps.
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    beta=st.floats(0.1, 2.0),
    gamma=st.floats(0.0, 0.95),
    pattern=st.lists(st.booleans(), min_size=1, max_size=12),
    momentum=st.sampled_from(["polyak", "nesterov"]),
)
def test_tracking_invariant(beta, gamma, pattern, momentum):
    n, d = 6, 5
    grad_fn, _ = quadratic_problem(n=n, d=d)
    cfg = DepositumConfig(alpha=0.05, beta=beta, gamma=gamma,
                          momentum=momentum, comm_period=3,
                          prox_name="l1", prox_kwargs={"lam": 1e-3})
    W = mixing_matrix("ring", n)
    mixer = MixPlan.dense(W)
    state = init(jnp.zeros(d), n)
    for comm in pattern:
        state, _ = step(state, None, grad_fn, cfg,
                        mixer if comm else identity_mixer, is_comm_step=comm)
        ybar = jnp.mean(state.y, axis=0)
        gbar = jnp.mean(state.g, axis=0)
        np.testing.assert_allclose(
            np.asarray(ybar), beta * np.asarray(gbar), rtol=2e-4, atol=1e-5
        )


# ---------------------------------------------------------------------------
# Convergence: deterministic grads => exact stationarity (Theorem 1, sigma=0)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("momentum", ["polyak", "nesterov", "none"])
@pytest.mark.parametrize("topology", ["ring", "complete", "star"])
def test_converges_to_stationary_point(momentum, topology):
    n = 10
    grad_fn, grad_fns = quadratic_problem(n=n)
    cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5, momentum=momentum,
                          comm_period=5, prox_name="l1",
                          prox_kwargs={"lam": 1e-2})
    rounds = 400 if topology != "star" else 900  # star: lambda ~ 1, slower
    state = run_rounds(cfg, n, grad_fn, rounds=rounds, topology=topology)
    m = stationarity_metrics(state, grad_fns, cfg)
    assert float(m["stationarity"]) < 1e-5, dict(m)


def test_weakly_convex_regularizer_converges():
    n = 10
    grad_fn, grad_fns = quadratic_problem(n=n)
    cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5, comm_period=5,
                          prox_name="mcp", prox_kwargs={"lam": 1e-2,
                                                        "theta": 4.0})
    state = run_rounds(cfg, n, grad_fn, rounds=400)
    m = stationarity_metrics(state, grad_fns, cfg)
    assert float(m["stationarity"]) < 1e-6


# ---------------------------------------------------------------------------
# Centralized equivalence: W=J, n clients, full-batch grads, gamma=0, beta=1,
# T0=1 ==> trajectory of xbar equals centralized proximal GD (with one-step
# gradient delay matching DEPOSITUM's update order).
# ---------------------------------------------------------------------------

def test_centralized_proximal_gd_equivalence():
    n, d = 4, 6
    grad_fn, _ = quadratic_problem(n=n, d=d, seed=3)
    alpha, lam = 0.08, 1e-2
    cfg = DepositumConfig(alpha=alpha, beta=1.0, gamma=0.0, momentum="none",
                          comm_period=1, prox_name="l1",
                          prox_kwargs={"lam": lam})
    W = mixing_matrix("complete", n)
    mixer = MixPlan.dense(W)
    state = init(jnp.zeros(d), n)

    from repro.core.prox import make_l1
    prox = make_l1(lam)

    # DEPOSITUM with y tracking: nu^{t+1} = y^t = mean grad at x^t (complete
    # graph).  Centralized analogue: z^{t+1} = prox(z^t - alpha * gbar(z^{t-1}))
    zs = [jnp.zeros(d)]
    g_prev = jnp.zeros(d)
    for t in range(30):
        state, _ = step(state, None, grad_fn, cfg, mixer, is_comm_step=True)
        z = prox.prox(zs[-1] - alpha * g_prev, alpha)
        g_prev = jnp.mean(grad_fn(jnp.broadcast_to(z, (n, d)), None)[0], 0)
        zs.append(z)
        xbar = jnp.mean(state.x, axis=0)
        np.testing.assert_allclose(np.asarray(xbar), np.asarray(z),
                                   rtol=1e-4, atol=1e-5)
        # consensus exact on the complete graph
        assert float(consensus_error(state.x)) < 1e-10


# ---------------------------------------------------------------------------
# Paper claim: sparsity — l1 regularised solution has exact zeros
# ---------------------------------------------------------------------------

def test_l1_induces_sparsity():
    n = 10
    grad_fn, _ = quadratic_problem(n=n)
    cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5, comm_period=5,
                          prox_name="l1", prox_kwargs={"lam": 0.5})
    state = run_rounds(cfg, n, grad_fn, rounds=300)
    xbar = np.asarray(jnp.mean(state.x, 0))
    assert (np.abs(xbar) < 1e-12).sum() > 0  # hard zeros from soft threshold


def test_gamma_zero_reduces_to_prox_dsgt():
    """momentum='polyak', gamma=0 must equal momentum='none' exactly."""
    n = 6
    grad_fn, _ = quadratic_problem(n=n)
    out = {}
    for mom, gamma in [("polyak", 0.0), ("none", 0.0)]:
        cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=gamma, momentum=mom,
                              comm_period=2, prox_name="l1",
                              prox_kwargs={"lam": 1e-3})
        out[mom] = run_rounds(cfg, n, grad_fn, rounds=20)
    np.testing.assert_allclose(np.asarray(out["polyak"].x),
                               np.asarray(out["none"].x), rtol=1e-6)
