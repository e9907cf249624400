"""MixPlan: mixing as a traced operand.

Every plan kind must agree with the numpy product W @ x of its dense W, a
plan must run exactly as its constant schedule, stacked plans must vmap
like stacked Hypers, and the torus circulant's documented divergence from
the grid-graph Metropolis W must hold exactly as stated.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DepositumConfig, init as dep_init, local_then_comm_round
from repro.core.gossip import (
    circulant_from_mixer_spec,
    torus_circulant_spec,
    torus_grid_shape,
)
from repro.core.mixing import (
    MixPlan,
    apply_mix,
    as_dense,
    plan_spectral_lambda,
    shard_body,
    stack_mixplans,
    validate_plan,
)
from repro.core.schedule import MixSchedule
from repro.core.topology import (
    mixing_matrix,
    spectral_lambda,
    torus_graph,
    validate_mixing,
)


def _x(n, d, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, d)), jnp.float32)


# ---------------------------------------------------------------------------
# kind-by-kind equivalence with the numpy product W @ x
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topology", ["ring", "star", "torus", "complete"])
def test_dense_plan_matches_dense_mixer(topology):
    n, d = 10, 7
    W = mixing_matrix(topology, n)
    x = _x(n, d)
    got = apply_mix(MixPlan.dense(W), {"p": x})["p"]
    ref = W.astype(np.float32) @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(3, 16), d=st.integers(1, 6))
def test_circulant_plan_matches_spec_dense(n, d):
    spec = [(+1, 1 / 3), (-1, 1 / 3)]
    plan = MixPlan.circulant(spec, 1 / 3)
    x = _x(n, d, seed=n * 7 + d)
    got = apply_mix(plan, x)
    W = circulant_from_mixer_spec(n, spec, 1 / 3)
    np.testing.assert_allclose(np.asarray(got), W @ np.asarray(x),
                               rtol=1e-5, atol=1e-6)
    # and densification reproduces the same matrix
    np.testing.assert_allclose(np.asarray(as_dense(plan, n).W), W, atol=1e-6)


def test_complete_and_identity_plans():
    n, d = 6, 4
    x = _x(n, d)
    out = apply_mix(MixPlan.complete(), x)
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(np.asarray(x).mean(0), (n, d)),
        rtol=1e-6, atol=1e-7)
    assert apply_mix(MixPlan.identity(), x) is x
    np.testing.assert_allclose(np.asarray(as_dense(MixPlan.complete(), n).W),
                               np.full((n, n), 1 / n), atol=1e-7)
    np.testing.assert_allclose(np.asarray(as_dense(MixPlan.identity(), n).W),
                               np.eye(n), atol=1e-7)


_ROUND_PLANS = {
    "dense": ("ring", lambda n: MixPlan.from_topology("ring", n)),
    "circulant": ("ring",
                  lambda n: MixPlan.from_topology("ring", n, prefer="sparse")),
    "complete": ("complete",
                 lambda n: MixPlan.from_topology("complete", n,
                                                 prefer="sparse")),
}


@pytest.mark.parametrize("T0", [1, 2])
@pytest.mark.parametrize("kind", sorted(_ROUND_PLANS))
def test_plan_runs_as_its_constant_schedule(kind, T0):
    """A plan is its constant schedule: the plan, ``MixSchedule.constant``
    of it and the backend's mixer of either give bit-identical rounds, and
    a trainer built from a topology lowers to the same program as one
    given that topology's constant schedule."""
    from repro.training.backends import StackedVmapBackend
    from repro.training.train_loop import FederatedTrainer, TrainerConfig

    n, d = 5, 6
    topology, make = _ROUND_PLANS[kind]
    plan = make(n)
    assert plan.kind == kind
    sched = MixSchedule.constant(plan)
    backend = StackedVmapBackend()
    A = _x(n * d, d, seed=3).reshape(n, d, d)

    def grad_fn(x, batch):
        return jnp.einsum("nij,nj->ni", A, x) - batch, {}

    cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.5,
                          momentum="polyak", comm_period=T0,
                          prox_name="l1", prox_kwargs={"lam": 1e-3})
    batches = _x(T0 * n, d, seed=4).reshape(T0, n, d)
    state0 = dep_init(jnp.zeros(d), n)
    outs = [jax.jit(lambda s, b, m=m: local_then_comm_round(
                s, b, grad_fn, cfg, m)[0])(state0, batches)
            for m in (plan, sched, backend.mixer_for(plan),
                      backend.mixer_for(sched))]
    for out in outs[1:]:
        for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    Aw = np.asarray(A[0])

    def init(key):
        return {"w": jnp.zeros((d,))}, None

    def loss(params, batch):
        r = Aw @ params["w"] - batch
        return jnp.mean(r * r), {}

    from repro.models.registry import Model

    model = Model(cfg=None, init=init, forward_train=None, loss=loss,
                  forward_decode=None, init_decode_cache=None)
    tc = TrainerConfig(n_clients=n, topology=topology, depositum=cfg)
    by_topology = FederatedTrainer(model, tc)
    by_schedule = FederatedTrainer(
        model, tc,
        schedule=MixSchedule.constant(MixPlan.from_topology(topology, n)))
    state = jax.eval_shape(lambda: dep_init({"w": jnp.zeros((d,))}, n))
    batch = jax.ShapeDtypeStruct((T0, n, d), jnp.float32)
    assert (by_topology.lower_round(state, batch).as_text()
            == by_schedule.lower_round(state, batch).as_text())


# ---------------------------------------------------------------------------
# stacked plans: the topology sweep axis
# ---------------------------------------------------------------------------

def test_stacked_plan_vmaps_like_per_point():
    n, d = 8, 5
    topos = ["complete", "ring", "star", "torus"]
    plans = [MixPlan.from_topology(t, n) for t in topos]
    stacked = stack_mixplans(plans)
    assert stacked.is_stacked and stacked.n_sweep == len(topos)
    x = _x(n, d)
    got = jax.vmap(lambda p: apply_mix(p, x), in_axes=(0,))(stacked)
    for s, p in enumerate(plans):
        np.testing.assert_allclose(np.asarray(got[s]),
                                   np.asarray(apply_mix(p, x)),
                                   rtol=1e-6, atol=1e-7)
        # point() inverts stacking
        np.testing.assert_allclose(np.asarray(stacked.point(s).W),
                                   np.asarray(p.W), atol=0)


def test_stacked_plan_lambda_and_validation():
    n = 9
    topos = ["complete", "ring", "star"]
    stacked = stack_mixplans([MixPlan.from_topology(t, n) for t in topos])
    lams = plan_spectral_lambda(stacked, n)
    for s, t in enumerate(topos):
        assert abs(lams[s] - spectral_lambda(mixing_matrix(t, n))) < 1e-6
    validate_plan(stacked, n)


def test_stack_rejects_heterogeneous_and_leafless():
    with pytest.raises(ValueError):
        stack_mixplans([MixPlan.dense(np.eye(3)),
                        MixPlan.circulant([(+1, 0.5)], 0.5)])
    with pytest.raises(ValueError):
        stack_mixplans([MixPlan.complete(), MixPlan.complete()])
    with pytest.raises(ValueError):
        stack_mixplans([])


def test_validate_plan_rejects_bad_matrix():
    bad = np.eye(4)  # disconnected
    with pytest.raises(ValueError):
        validate_plan(MixPlan.dense(bad), 4)


def test_plan_is_jit_operand_no_retrace():
    """Changing W must NOT retrace: the whole point of the refactor."""
    n, d = 6, 4
    traces = []

    @jax.jit
    def f(plan, x):
        traces.append(1)
        return apply_mix(plan, x)

    x = _x(n, d)
    f(MixPlan.dense(mixing_matrix("ring", n)), x)
    f(MixPlan.dense(mixing_matrix("star", n)), x)
    f(MixPlan.dense(mixing_matrix("torus", n)), x)
    assert len(traces) == 1


# ---------------------------------------------------------------------------
# torus circulant vs grid torus (documented approximation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [6, 8, 10, 12, 15])
def test_torus_mixer_equals_its_circulant_dense_W(n):
    """The circulant torus plan — one ppermute per offset over a named
    axis, and the stacked rolls — must equal W @ x for
    circulant_from_mixer_spec's W, including NON-square grids and the
    n == 2b coincident-offset case."""
    offsets_weights, self_w = torus_circulant_spec(n)
    plan = MixPlan.circulant(offsets_weights, self_w)
    d = 3
    x = _x(n, d, seed=n)
    W = circulant_from_mixer_spec(n, offsets_weights, self_w)
    permuted = jax.vmap(lambda xi: shard_body(plan, xi, "c", n),
                        axis_name="c")(x)
    for got in (permuted, apply_mix(plan, x)):
        np.testing.assert_allclose(np.asarray(got), W @ np.asarray(x),
                                   rtol=1e-5, atol=1e-6)
    # the circulant W itself satisfies Assumption 2
    validate_mixing(W)


@pytest.mark.parametrize("n", [6, 8, 12, 15])
def test_torus_circulant_documented_divergence_from_grid(n):
    """The circulant torus is a DIFFERENT graph from torus_graph's grid
    Metropolis W whenever b < n (every non-degenerate factorisation) —
    the docs promise this divergence; pin it so nobody 'fixes' one side."""
    a, b = torus_grid_shape(n)
    assert a >= 2, "test ns must factorise"
    offsets_weights, self_w = torus_circulant_spec(n)
    Wc = circulant_from_mixer_spec(n, offsets_weights, self_w)
    Wg = torus_graph(n)
    assert np.abs(Wc - Wg).max() > 1e-3
    # both are valid Assumption-2 matrices on degree<=4 wrap-around graphs
    validate_mixing(Wc)
    validate_mixing(Wg)


def test_torus_coincident_offsets_accumulate():
    """n = 2b: +b and -b are the same edge; its weight doubles to 2/5."""
    n = 8  # a=2, b=4
    offsets_weights, self_w = torus_circulant_spec(n)
    W = circulant_from_mixer_spec(n, offsets_weights, self_w)
    assert abs(W[0, 4] - 0.4) < 1e-12
    validate_mixing(W)


# ---------------------------------------------------------------------------
# erdos_renyi regression (the dead retry loop)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [0.0, 0.2, 0.8])
@pytest.mark.parametrize("seed", [0, 1, 7])
def test_erdos_renyi_validates_for_any_draw(p, seed):
    """The ring backbone guarantees connectivity for every (p, seed) —
    including p=0, where the graph degenerates to exactly the ring — and
    the builder itself runs validate_mixing before returning."""
    from repro.core.topology import erdos_renyi_graph

    W = erdos_renyi_graph(8, p=p, seed=seed)
    validate_mixing(W)
    if p == 0.0:
        np.testing.assert_allclose(W, mixing_matrix("ring", 8), atol=1e-12)


def test_erdos_renyi_seed_variation():
    from repro.core.topology import erdos_renyi_graph

    W0 = erdos_renyi_graph(10, p=0.5, seed=0)
    W1 = erdos_renyi_graph(10, p=0.5, seed=1)
    assert np.abs(W0 - W1).max() > 1e-6  # different draws, both valid
    validate_mixing(W0)
    validate_mixing(W1)
