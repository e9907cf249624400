"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

Blockwise online-softmax: grid (batch, q_heads, Lq/BQ, Lk/BK) with the last
dim "arbitrary" (sequential) — running max/sum/accumulator live in VMEM
scratch and the output block is written once on the final k step.  K/V blocks
for a q head h come from kv head ``h // (H // KV)`` via the BlockSpec index
map, so GQA never materialises repeated K/V.

MXU alignment: D and the block sizes are multiples of 128 (q/k tiles hit the
128x128 systolic array); masking is done pre-softmax in fp32.

Validated with ``interpret=True`` on CPU against ``ref.py``; on TPU the same
call lowers to Mosaic.  A production variant would also skip fully-masked
K blocks by shrinking the grid per q row; we keep the full rectangular grid
(correct, simpler) and note the skip as a TPU-perf refinement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, causal, window, block_q, block_k, n_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, :, 0, :].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)          # (BK, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                          # (BQ, BK)

    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = mask & (cols <= rows)
        if window > 0:
            mask = mask & (cols > rows - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (BQ,)
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (exp(NEG_INF - NEG_INF) -> exp(0)=1 is wrong)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)

    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1)
    acc_ref[...] = alpha[:, None] * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, :, 0, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k")
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK):
    """q: (B, Lq, H, D); k, v: (B, Lk, KV, D) -> (B, Lq, H, D)."""
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    group = H // KV
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    assert Lq % block_q == 0 and Lk % block_k == 0, (Lq, block_q, Lk, block_k)
    n_q, n_k = Lq // block_q, Lk // block_k
    scale = 1.0 / (D ** 0.5)

    grid = (B, H, n_q, n_k)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec(
                (1, block_k, 1, D), lambda b, h, i, j: (b, j, h // group, 0)
            ),
            pl.BlockSpec(
                (1, block_k, 1, D), lambda b, h, i, j: (b, j, h // group, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
    return out

