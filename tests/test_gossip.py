"""Gossip: dense plans vs circulant neighbor specs must agree."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.gossip import circulant_from_mixer_spec
from repro.core.mixing import MixPlan, apply_mix
from repro.core.topology import mixing_matrix, validate_mixing


@settings(max_examples=25, deadline=None)
@given(n=st.integers(3, 12), d=st.integers(1, 8), seed=st.integers(0, 100))
def test_dense_mixer_matches_matmul(n, d, seed):
    W = mixing_matrix("ring", n)
    x = np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)
    out = apply_mix(MixPlan.dense(W), {"p": jnp.asarray(x)})["p"]
    np.testing.assert_allclose(np.asarray(out), W @ x, rtol=1e-5, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(3, 16))
def test_ring_circulant_is_metropolis_ring(n):
    """The ppermute spec (+1,1/3),(-1,1/3),self 1/3 equals the Metropolis W."""
    W_spec = circulant_from_mixer_spec(n, [(+1, 1 / 3), (-1, 1 / 3)], 1 / 3)
    W = mixing_matrix("ring", n)
    np.testing.assert_allclose(W_spec, W, atol=1e-12)
    validate_mixing(W_spec)


def test_mixing_preserves_mean():
    """Doubly stochastic => client mean invariant (tracking survives gossip)."""
    n, d = 8, 5
    W = mixing_matrix("ring", n)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, d)),
                    jnp.float32)
    out = apply_mix(MixPlan.dense(W), x)
    np.testing.assert_allclose(np.asarray(jnp.mean(out, 0)),
                               np.asarray(jnp.mean(x, 0)), rtol=1e-5,
                               atol=1e-6)
