"""Beyond-paper extensions: Chebyshev-accelerated gossip and time-varying
(partial-participation) mixing."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (
    DepositumConfig,
    init,
    MixPlan,
    mixing_matrix,
    spectral_lambda,
    step,
)
from repro.core.topology import chebyshev_matrix, lazy_subgraph_matrix


@settings(max_examples=15, deadline=None)
@given(n=st.integers(6, 24), k=st.integers(2, 5))
def test_chebyshev_shrinks_spectral_radius(n, k):
    W = mixing_matrix("ring", n)
    P = chebyshev_matrix(W, k)
    # mean preservation (rows sum to one, symmetric)
    np.testing.assert_allclose(P.sum(1), 1.0, atol=1e-8)
    np.testing.assert_allclose(P, P.T, atol=1e-8)
    lamW, lamP = spectral_lambda(W), spectral_lambda(P)
    assert lamP < lamW ** 1.5  # much better than one exchange
    # and strictly better than k plain exchanges would suggest per-exchange
    assert lamP <= lamW + 1e-9


def test_chebyshev_beats_plain_powers():
    """P_k(W) contracts consensus faster than W^k round-for-round? No —
    faster than W per exchange-budget: lambda(P_k)^(1/k) < lambda(W)."""
    W = mixing_matrix("ring", 16)
    for k in (2, 3, 4):
        P = chebyshev_matrix(W, k)
        assert spectral_lambda(P) ** (1.0 / k) < spectral_lambda(W) + 1e-9


def test_chebyshev_preserves_tracking_invariant():
    """J y = beta J g must survive a (possibly negative-entry) mixing."""
    n, d, beta = 8, 5, 0.7
    W = mixing_matrix("ring", n)
    P = chebyshev_matrix(W, 3)
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d, d))
    A = jnp.einsum("nij,nkj->nik", A, A) / d + 0.5 * jnp.eye(d)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, d))

    def grad_fn(x, batch):
        return jnp.einsum("nij,nj->ni", A, x) - b, {}

    cfg = DepositumConfig(alpha=0.05, beta=beta, gamma=0.5, comm_period=1,
                          prox_name="l1", prox_kwargs={"lam": 1e-3})
    state = init(jnp.zeros(d), n)
    mixer = MixPlan.dense(P)
    for _ in range(6):
        state, _ = step(state, None, grad_fn, cfg, mixer, is_comm_step=True)
        np.testing.assert_allclose(
            np.asarray(jnp.mean(state.y, 0)),
            beta * np.asarray(jnp.mean(state.g, 0)), rtol=2e-4, atol=1e-6)


def test_chebyshev_converges_faster_on_ring():
    """Consensus error after equal comm rounds: chebyshev(3) < plain W."""
    n, d = 16, 8
    W = mixing_matrix("ring", n)
    P = chebyshev_matrix(W, 3)
    x0 = np.random.default_rng(0).standard_normal((n, d))
    xw, xp = x0.copy(), x0.copy()
    for _ in range(10):
        xw = W @ xw
        xp = P @ xp
    err = lambda x: np.linalg.norm(x - x.mean(0, keepdims=True))
    assert err(xp) < err(xw) * 0.2


@settings(max_examples=20, deadline=None)
@given(n=st.integers(4, 16), seed=st.integers(0, 50))
def test_partial_participation_matrix_valid(n, seed):
    """Remark 3: lazy subgraph mixing stays symmetric doubly stochastic."""
    rng = np.random.default_rng(seed)
    W = mixing_matrix("ring", n)
    active = rng.random(n) < 0.7
    Wt = lazy_subgraph_matrix(W, active)
    np.testing.assert_allclose(Wt.sum(1), 1.0, atol=1e-10)
    np.testing.assert_allclose(Wt, Wt.T, atol=1e-10)
    assert (Wt >= -1e-12).all()
    # inactive clients do not mix at all
    for i in range(n):
        if not active[i]:
            assert Wt[i, i] == 1.0


def test_partial_participation_preserves_mean():
    n, d = 10, 4
    W = mixing_matrix("complete", n)
    active = np.asarray([True] * 5 + [False] * 5)
    Wt = lazy_subgraph_matrix(W, active)
    x = np.random.default_rng(1).standard_normal((n, d))
    np.testing.assert_allclose((Wt @ x).mean(0), x.mean(0), atol=1e-10)


# ---------------------------------------------------------------------------
# validate_plan over lazy matrices (property tests, propcheck-compatible)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 16), seed=st.integers(0, 40),
       p=st.floats(0.0, 1.0), topology=st.sampled_from(["ring", "star",
                                                        "torus"]))
def test_lazy_plan_passes_validate_plan(n, seed, p, topology):
    """For ANY participation draw, the lazy matrix is a valid (possibly
    non-contracting) mixing plan: symmetric, doubly stochastic, nonnegative.
    ``validate_plan(..., connected=False)`` is the per-round gate the
    schedule machinery applies (a single lazy round need not contract)."""
    from repro.core import MixPlan, validate_plan

    W = mixing_matrix(topology, n)
    active = np.random.default_rng(seed).random(n) < p
    Wt = lazy_subgraph_matrix(W, active)
    validate_plan(MixPlan.dense(Wt), n, connected=False)
    np.testing.assert_allclose(Wt, Wt.T, atol=1e-10)
    np.testing.assert_allclose(Wt.sum(0), 1.0, atol=1e-10)  # columns too
    assert (Wt >= -1e-12).all()


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 16), topology=st.sampled_from(["ring", "star",
                                                       "torus", "complete"]))
def test_lazy_all_active_recovers_W_exactly(n, topology):
    """Full participation must reproduce W entry-for-entry — the identity
    the schedule equivalence tests (p_active=1.0 == static plan) rest on."""
    W = mixing_matrix(topology, n)
    Wt = lazy_subgraph_matrix(W, np.ones(n, dtype=bool))
    np.testing.assert_allclose(Wt, W, atol=1e-12)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 12), seed=st.integers(0, 20),
       rounds=st.integers(1, 6))
def test_lazy_schedule_validates_per_round(n, seed, rounds):
    """MixSchedule.lazy wires the same masks through validate_schedule:
    every pre-drawn round matrix passes the Assumption-2 (minus
    contraction) gate, and the traced execution equals the host matrix."""
    import jax.numpy as jnp
    from repro.core import MixPlan, MixSchedule, apply_schedule, \
        validate_schedule

    W = mixing_matrix("ring", n)
    sched = MixSchedule.lazy(MixPlan.dense(W), 0.5, rounds=rounds, seed=seed)
    validate_schedule(sched, n)
    x = jnp.asarray(np.random.default_rng(seed + 1).standard_normal((n, 3)),
                    jnp.float32)
    r = seed % rounds
    Wt = lazy_subgraph_matrix(W, np.asarray(sched.active[r]) > 0.5)
    np.testing.assert_allclose(np.asarray(apply_schedule(sched, r, x)),
                               Wt @ np.asarray(x), rtol=1e-5, atol=1e-6)
