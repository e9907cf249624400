"""Hyperparameters as traced values: prox identities must hold when
alpha/lam/theta are jnp scalars flowing through jit (including with donated
state), and the fused Pallas path must match ref.py after the refactor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DepositumConfig,
    Hyper,
    init as dep_init,
    MixPlan,
    mixing_matrix,
    prox_apply,
    step,
)
from repro.core.prox import get_family, soft_threshold
from repro.kernels.prox.ops import fused_update_tree, prox_tree
from repro.kernels.prox import ref


# ---------------------------------------------------------------------------
# prox identities under traced scalars
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lam,alpha", [(1e-3, 0.05), (0.2, 0.3), (1.0, 0.01)])
def test_l1_soft_threshold_identity_traced(lam, alpha):
    """jit(prox_apply) with traced alpha/lam == closed-form soft threshold,
    with zero recompilation across hyperparameter values."""
    x = jax.random.normal(jax.random.PRNGKey(0), (257,))

    @jax.jit
    def f(x, alpha, lam):
        return prox_apply("l1", x, alpha, lam=lam)

    out = f(x, jnp.float32(alpha), jnp.float32(lam))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(soft_threshold(x, alpha * lam)),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("name", ["l1", "l2sq", "mcp", "scad"])
def test_prox_fixed_point_identity_traced(name):
    """prox_{alpha h}(z) = z whenever z is already the prox of something and
    we re-apply with the *same* traced parameters to the optimality-shifted
    input: for separable h, z = prox(x) minimises h + (1/2a)||.-x||^2, so
    prox(z + a*grad_quad) = z with grad_quad = (x - z)/a ... i.e.
    prox(x) == prox(prox(x) + (x - prox(x))) exactly at the same params.

    Checked in the weaker, robust form prox(prox(x)) stays close to a prox
    fixed point for shrinkage operators; exact for l2sq scaling identity.
    """
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (129,)) * 2.0
    alpha, lam, theta = jnp.float32(0.1), jnp.float32(0.05), jnp.float32(4.0)

    @jax.jit
    def p(v, alpha, lam, theta):
        return prox_apply(name, v, alpha, lam=lam, theta=theta)

    z = p(x, alpha, lam, theta)
    if name == "l2sq":
        # exact fixed-point identity: prox(x*(1+alpha*lam)) == x
        back = p(x * (1.0 + alpha * lam), alpha, lam, theta)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   rtol=1e-5, atol=1e-6)
    else:
        # thresholding maps: a second application moves each coordinate by
        # at most the first-step threshold alpha*lam (up to the weakly
        # convex rescale), and large coordinates are exact fixed points
        z2 = p(z, alpha, lam, theta)
        thr = float(alpha * lam) * (1.0 + float(alpha))
        assert float(jnp.max(jnp.abs(z2 - z))) <= thr + 1e-6
        if name in ("mcp", "scad"):
            # beyond the knee the nonconvex penalties are flat: identity
            big = jnp.abs(x) > theta * lam * (1.0 + float(alpha))
            np.testing.assert_allclose(np.asarray(z[big]), np.asarray(x[big]),
                                       rtol=1e-6, atol=1e-7)


def test_prox_under_jit_with_donated_state():
    """Traced hypers compose with buffer donation on the state operand."""
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 33))

    @jax.jit
    def f(x, hyper):
        return prox_apply("scad", x, hyper.alpha, lam=hyper.lam,
                          theta=hyper.theta)

    f_donated = jax.jit(
        lambda x, hyper: prox_apply("scad", x, hyper.alpha, lam=hyper.lam,
                                    theta=hyper.theta),
        donate_argnums=(0,),
    )
    h = Hyper.create(alpha=0.2, lam=0.1, theta=3.0)
    want = f(x, h)
    got = f_donated(x, h)  # x's buffer may be reused; result must be equal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-7)


def test_step_with_donated_state_and_traced_hyper():
    """A full DEPOSITUM step jits with donated state + traced Hyper operand
    and matches the config-floats path."""
    n, d = 4, 24
    A = jax.random.normal(jax.random.PRNGKey(3), (n, d))

    def grad_fn(x, batch):
        return A * x, {}

    cfg = DepositumConfig(alpha=0.07, beta=0.9, gamma=0.4, comm_period=1,
                          prox_name="l1", prox_kwargs={"lam": 1e-3})
    mixer = MixPlan.dense(mixing_matrix("ring", n))

    stepped = jax.jit(
        lambda st, hyper: step(st, None, grad_fn, cfg, mixer,
                               is_comm_step=True, hyper=hyper)[0],
        donate_argnums=(0,),
    )
    # dep_init shares one zeros buffer across y/nu/mu/g; donation requires
    # distinct buffers, so materialise copies first
    st0 = jax.tree_util.tree_map(jnp.array, dep_init(jnp.ones(d), n))
    got = stepped(st0, cfg.hyper())

    want = step(dep_init(jnp.ones(d), n), None, grad_fn, cfg, mixer,
                is_comm_step=True)[0]
    for name in ("x", "y", "nu", "g"):
        np.testing.assert_allclose(np.asarray(getattr(got, name)),
                                   np.asarray(getattr(want, name)),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_no_recompile_across_hyper_values():
    """The same jitted step must serve different hyper values (the whole
    point of the Hyper split): trace count stays at 1."""
    n, d = 3, 16
    traces = []

    def grad_fn(x, batch):
        traces.append(1)
        return x, {}

    cfg = DepositumConfig(comm_period=1, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    mixer = MixPlan.dense(mixing_matrix("complete", n))
    stepped = jax.jit(
        lambda st, hyper: step(st, None, grad_fn, cfg, mixer,
                               is_comm_step=True, hyper=hyper)[0]
    )
    st = dep_init(jnp.ones(d), n)
    for a in (0.01, 0.05, 0.2, 0.33):
        st = stepped(st, Hyper.create(alpha=a, beta=1.0, gamma=0.5, lam=1e-3))
    assert sum(traces) == 1, f"retraced {sum(traces)} times"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hyper_scalars_preserve_param_dtype(dtype):
    """Strong f32 Hyper scalars must not promote bf16 state (the scan carry
    in local_then_comm_round would change type and error)."""
    from repro.core import local_then_comm_round

    n, d, T0 = 3, 16, 3
    A = jax.random.normal(jax.random.PRNGKey(6), (n, d)).astype(dtype)

    def grad_fn(x, batch):
        return (A * x).astype(dtype), {}

    cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.6, momentum="nesterov",
                          comm_period=T0, prox_name="mcp",
                          prox_kwargs={"lam": 1e-3, "theta": 4.0})
    mixer = MixPlan.dense(mixing_matrix("ring", n))
    st = dep_init(jnp.ones(d, dtype), n)
    rnd = jax.jit(lambda st, hyper: local_then_comm_round(
        st, jnp.zeros((T0, 1)), grad_fn, cfg, mixer, hyper=hyper)[0])
    out = rnd(st, cfg.hyper())
    for name in ("x", "y", "nu", "mu", "g"):
        assert getattr(out, name).dtype == dtype, name


# ---------------------------------------------------------------------------
# fused Pallas path with traced scalars
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["l1", "mcp", "scad"])
def test_fused_tree_matches_ref_with_traced_scalars(kind):
    key = jax.random.PRNGKey(4)
    mk = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s) * 0.1
    tree = {"w": mk(0, (40, 65)), "b": mk(1, (17,))}
    y = {"w": mk(2, (40, 65)), "b": mk(3, (17,))}
    nu = {"w": mk(4, (40, 65)), "b": mk(5, (17,))}
    lam, theta = jnp.float32(5e-3), jnp.float32(4.0)
    alpha, gamma = jnp.float32(0.15), jnp.float32(0.7)

    @jax.jit
    def fused(tree, y, nu, lam, theta, alpha, gamma):
        return fused_update_tree(tree, y, nu, kind=kind, lam=lam, theta=theta,
                                 alpha=alpha, gamma=gamma)

    xs, nus = fused(tree, y, nu, lam, theta, alpha, gamma)
    for k in tree:
        xr, nur = ref.fused_update_ref(tree[k], y[k], nu[k], float(lam),
                                       float(alpha), float(gamma),
                                       prox_kind=kind, theta=float(theta))
        np.testing.assert_allclose(np.asarray(xs[k]), np.asarray(xr),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(nus[k]), np.asarray(nur),
                                   rtol=1e-5, atol=1e-6)


def test_fused_kernel_vmaps_over_lam_axis():
    """One kernel compilation serves a whole stacked-lam sweep via vmap."""
    x = jax.random.normal(jax.random.PRNGKey(5), (300,)) * 0.1
    lams = jnp.asarray([1e-4, 1e-2, 0.3], jnp.float32)

    outs = jax.vmap(
        lambda lam: prox_tree(x, kind="l1", lam=lam, alpha=0.5)
    )(lams)
    for i, lam in enumerate(np.asarray(lams)):
        np.testing.assert_allclose(
            np.asarray(outs[i]),
            np.asarray(ref.prox_l1_ref(x, float(lam), 0.5)),
            rtol=1e-5, atol=1e-7)
