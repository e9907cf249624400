"""Byzantine-resilient and compressed gossip extensions."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DepositumConfig,
    MixPlan,
    apply_mix,
    init,
    mixing_matrix,
    step,
)
from repro.core.extensions import (
    compressed_gossip_round,
    init_compressed,
    make_trimmed_mean_mixer,
    topk_compress,
)


def test_trimmed_mean_equals_mean_without_outliers():
    n, d = 8, 5
    W = mixing_matrix("complete", n)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                    jnp.float32)
    mixer = make_trimmed_mean_mixer(W, trim=1)
    out = mixer(x, 0)
    # complete graph: trimmed mean of all clients per coordinate
    ref = []
    xs = np.sort(np.asarray(x), axis=0)
    ref = xs[1:-1].mean(0)
    np.testing.assert_allclose(np.asarray(out[0]), ref, rtol=1e-5, atol=1e-6)


def test_trimmed_mean_survives_byzantine_client():
    """One client broadcasts garbage; trimmed mean ignores it, plain mean
    gets dragged."""
    n, d = 10, 6
    W = mixing_matrix("complete", n)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x[3] = 1e6  # Byzantine
    xj = jnp.asarray(x)

    robust = make_trimmed_mean_mixer(W, trim=1)(xj, 0)
    plain = apply_mix(MixPlan.dense(W), xj)
    honest_mean = x[np.arange(n) != 3].mean(0)
    assert float(jnp.max(jnp.abs(robust[0] - honest_mean))) < 1.0
    assert float(jnp.max(jnp.abs(plain[0] - honest_mean))) > 1e4


def test_trimmed_mean_depositum_converges_under_attack():
    """DEPOSITUM + trimmed-mean gossip still reaches a good region with a
    Byzantine client injecting huge gradients."""
    n, d = 10, 8
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, d, d))
    A = jnp.einsum("nij,nkj->nik", A, A) / d + 0.5 * jnp.eye(d)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    attack = jnp.zeros((n, 1)).at[0].set(1.0)

    def grad_fn(x, batch):
        g = jnp.einsum("nij,nj->ni", A, x) - b
        return g + attack * 1e4, {}          # client 0 poisons its gradient

    W = mixing_matrix("complete", n)
    cfg = DepositumConfig(alpha=0.05, beta=1.0, gamma=0.0, momentum="none",
                          comm_period=1, prox_name="l1",
                          prox_kwargs={"lam": 1e-3})
    honest = slice(1, n)

    def run(mixer):
        st = init(jnp.zeros(d), n)
        for _ in range(150):
            st, _ = step(st, None, grad_fn, cfg, mixer, is_comm_step=True)
        xbar = jnp.mean(st.x[honest], 0)
        # honest-objective gradient norm at the honest consensus
        g = jnp.einsum("nij,j->ni", A[honest], xbar) - b[honest]
        return float(jnp.linalg.norm(jnp.mean(g, 0))), float(
            jnp.max(jnp.abs(xbar)))

    g_rob, mag_rob = run(make_trimmed_mean_mixer(W, trim=1))
    g_pln, mag_pln = run(MixPlan.dense(W))
    assert mag_rob < 10.0, mag_rob            # robust stays bounded
    assert mag_pln > 10.0 or g_pln > g_rob    # plain gets poisoned
    assert g_rob < 2.0, g_rob


def test_topk_keeps_largest():
    x = jnp.asarray([[1.0, -5.0, 0.1, 3.0]])
    out = np.asarray(topk_compress(x, 2))
    np.testing.assert_allclose(out, [[0.0, -5.0, 0.0, 3.0]])


def test_compressed_consensus_converges():
    """CHOCO-gossip rounds drive consensus with ~12% of dense traffic."""
    n, d = 8, 64
    W = mixing_matrix("ring", n)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    target = np.asarray(x).mean(0)
    st = init_compressed(x)
    frac = None
    for _ in range(400):
        x, st, frac = compressed_gossip_round(x, st, W, k=8, step=0.3)
    err = float(jnp.max(jnp.abs(x - jnp.asarray(target))))
    assert err < 0.05, err
    assert frac == 8 / 64
    # mean preserved throughout (doubly stochastic mixing of xhat)
    np.testing.assert_allclose(np.asarray(jnp.mean(x, 0)), target, atol=1e-2)


def test_compressed_round_equals_plan_leaf_path():
    """Old-vs-new: the deprecated ``compressed_gossip_round`` and the
    plan-leaf CHOCO exchange (``choco_mix`` with the incremental running
    mix ``s``) follow the same trajectory — bit-exact on round one (zero
    memory), fp-tolerance after (fresh ``W @ xhat`` vs accumulated s)."""
    from repro.core import (
        CompressionSpec,
        MixPlan,
        apply_mix,
        choco_mix,
        comm_memory,
    )

    n, d, k = 8, 64, 8
    W = mixing_matrix("ring", n)
    x0 = jnp.asarray(np.random.default_rng(2).standard_normal((n, d)),
                     jnp.float32)
    spec = CompressionSpec.topk(k / d, ef_step=0.3)
    plan = MixPlan.dense(jnp.asarray(W, jnp.float32))
    mixfn = lambda t: apply_mix(plan, t)  # noqa: E731

    x_old, st = x0, init_compressed(x0)
    x_new, mem = x0, comm_memory(x0)
    for i in range(50):
        x_old, st, _ = compressed_gossip_round(x_old, st, W, k, step=0.3)
        x_new, mem = choco_mix(spec, mixfn, x_new, mem)
        if i == 0:
            np.testing.assert_array_equal(np.asarray(x_old),
                                          np.asarray(x_new))
    np.testing.assert_allclose(np.asarray(x_old), np.asarray(x_new),
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st.xhat), np.asarray(mem.xhat),
                               rtol=1e-5, atol=2e-5)


def test_compression_memory_matters():
    """Naive sparsified gossip (mix C(x) directly, no xhat memory) loses the
    untransmitted mass and cannot reach the true mean."""
    n, d = 8, 64
    W = mixing_matrix("ring", n)
    x0 = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                     jnp.float32)
    target = np.asarray(x0).mean(0)

    # CHOCO (with memory)
    x, st = x0, init_compressed(x0)
    for _ in range(400):
        x, st, _ = compressed_gossip_round(x, st, W, k=8, step=0.3)
    err_choco = float(jnp.max(jnp.abs(x - jnp.asarray(target))))

    # naive: x <- x + step (W - I) C(x)
    Wj = jnp.asarray(W, jnp.float32)
    xn = x0
    for _ in range(400):
        c = topk_compress(xn, 8)
        xn = xn + 0.3 * (jnp.einsum("ij,j...->i...", Wj, c) - c)
    err_naive = float(jnp.max(jnp.abs(xn - jnp.asarray(target))))
    assert err_choco < err_naive * 0.5, (err_choco, err_naive)
