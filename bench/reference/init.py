"""Weights from a seed, in the layout the program's models use.

Each family module lists its leaves as ``(path, shape, law)``; one jitted
call draws them all on the device from the seed, in the configuration's
dtype.  The harness hands the result to the program, and the reference
draws the same weights again from the same seed, so neither takes anything
from the other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (the low and high 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.fold_in(jax.random.key(0), np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def draw(key, shape, law):
    """One leaf: ("normal", scale) | ("zeros",) | ("ones",) |
    ("dt_bias", lo, hi) | ("a_log", lo, hi), the last two per head on the
    last axis (broadcast over leading layer axes)."""
    kind = law[0]
    if kind == "normal":
        return law[1] * jax.random.normal(key, shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "dt_bias":   # softplus^-1 of a linear ramp of step sizes
        ramp = jnp.linspace(law[1], law[2], shape[-1])
        return jnp.broadcast_to(jnp.log(jnp.expm1(ramp)), shape)
    if kind == "a_log":     # log of a linear ramp of decay rates
        return jnp.broadcast_to(jnp.log(jnp.linspace(law[1], law[2],
                                                     shape[-1])), shape)
    raise ValueError(f"unknown law {law!r}")


def build(leaves, key, dtype):
    """Nested dict of leaves drawn from ``key``; leaf i uses fold_in(key, i)."""
    out: dict = {}
    for i, (path, shape, law) in enumerate(leaves):
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = draw(jax.random.fold_in(key, i), shape,
                              law).astype(dtype)
    return out


def make_init(family_module, cfg: dict, dtype):
    """jitted ``key -> params`` of one configuration.

    One executable makes the weights for both sides of a run: on a TPU two
    differently fused programs may evaluate ``log`` or the normal sampler's
    inverse ``erf`` an f32 ulp apart, and so round a few weights to
    neighbouring bf16 values."""
    leaves = family_module.leaves(cfg)
    return jax.jit(lambda key: build(leaves, key, dtype))
