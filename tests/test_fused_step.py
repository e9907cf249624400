"""use_fused_kernel path of DEPOSITUM must equal the reference path exactly
(kernel validated in interpret mode on CPU; lowers to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DepositumConfig,
    init,
    MixPlan,
    mixing_matrix,
    step,
)


@pytest.mark.parametrize("prox,kwargs", [
    ("l1", {"lam": 1e-2}),
    ("mcp", {"lam": 1e-2, "theta": 4.0}),
    ("scad", {"lam": 1e-2, "theta": 4.0}),
])
def test_fused_step_matches_reference(prox, kwargs):
    n, d = 6, 777
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d))
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, d))

    def grad_fn(x, batch):
        return A * x - b, {}

    W = mixing_matrix("ring", n)
    mixer = MixPlan.dense(W)
    out = {}
    for fused in (False, True):
        cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.8,
                              momentum="polyak", comm_period=2,
                              prox_name=prox, prox_kwargs=kwargs,
                              use_fused_kernel=fused)
        st = init(jnp.zeros(d), n)
        for t in range(6):
            st, _ = step(st, None, grad_fn, cfg, mixer,
                         is_comm_step=(t % 2 == 1))
        out[fused] = st
    for name in ("x", "nu", "y", "g"):
        np.testing.assert_allclose(
            np.asarray(getattr(out[False], name)),
            np.asarray(getattr(out[True], name)), atol=1e-5, rtol=1e-5)


def test_fused_step_masked_generic_mixer_matches_reference():
    """An explicit active_mask with a *generic* dense mixer must keep the
    reference compute-then-select order (active rows read frozen rows'
    hypothetical halves through W): the fused path withholds the in-kernel
    gate there and must still match the unfused path exactly."""
    n, d = 6, 129
    key = jax.random.PRNGKey(5)
    A = jax.random.normal(key, (n, d))

    def grad_fn(x, batch):
        return A * x, {}

    mixer = MixPlan.dense(mixing_matrix("ring", n))
    mask = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
    out = {}
    for fused in (False, True):
        cfg = DepositumConfig(alpha=0.05, gamma=0.8, momentum="polyak",
                              comm_period=1, prox_name="l1",
                              prox_kwargs={"lam": 1e-3},
                              use_fused_kernel=fused)
        st = init(jnp.ones(d), n)
        for t in range(4):
            st, _ = step(st, None, grad_fn, cfg, mixer,
                         is_comm_step=(t % 2 == 1), active_mask=mask)
        out[fused] = st
    for name in ("x", "nu", "y", "g"):
        np.testing.assert_allclose(
            np.asarray(getattr(out[False], name)),
            np.asarray(getattr(out[True], name)), atol=1e-5, rtol=1e-5,
            err_msg=f"leaf {name}")
    # frozen rows never moved off their init values on either path
    np.testing.assert_array_equal(np.asarray(out[True].nu)[jnp.asarray(
        [1, 4])], 0.0)


def test_fused_falls_back_for_nesterov():
    """Nesterov needs mu; the fused kernel only covers Polyak — the step
    must silently use the reference path (and still be correct)."""
    n, d = 4, 64
    key = jax.random.PRNGKey(2)
    A = jax.random.normal(key, (n, d))

    def grad_fn(x, batch):
        return A * x, {}

    W = mixing_matrix("complete", n)
    mixer = MixPlan.dense(W)
    out = {}
    for fused in (False, True):
        cfg = DepositumConfig(alpha=0.05, gamma=0.5, momentum="nesterov",
                              comm_period=1, prox_name="l1",
                              prox_kwargs={"lam": 1e-3},
                              use_fused_kernel=fused)
        st = init(jnp.ones(d), n)
        for _ in range(4):
            st, _ = step(st, None, grad_fn, cfg, mixer, is_comm_step=True)
        out[fused] = st
    np.testing.assert_allclose(np.asarray(out[False].x),
                               np.asarray(out[True].x), atol=1e-6)
