"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

Selective SSM with scalar-per-head decay A, discretised as

    h_t = exp(dt_t A) h_{t-1} + dt_t * B_t x_t^T     (state: (N, P) per head)
    y_t = C_t h_t + D x_t

Training/prefill use the *chunked* SSD algorithm: quadratic attention-like
compute inside chunks of Q tokens + a linear inter-chunk recurrence
(``lax.scan`` over chunks).  Decode is the O(1) recurrence.

ngroups = 1 (B/C shared across heads), matching the published 130M config.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import Initializer, rms_norm


def init_mamba(ini: Initializer, cfg: ModelConfig):
    d = cfg.d_model
    di = cfg.ssm_inner
    H, N, W = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv_width
    conv_ch = di + 2 * N
    p = {
        # fused input projection: [z(di), x(di), B(N), C(N), dt(H)]
        "in_proj": ini.normal((d, 2 * di + 2 * N + H), ("embed", "ssm_inner")),
        "conv_w": ini.normal((W, conv_ch), (None, "ssm_inner"), scale=0.5),
        "conv_b": ini.zeros((conv_ch,), ("ssm_inner",)),
        "dt_bias": ini.const(
            jnp.log(jnp.expm1(jnp.linspace(1e-3, 0.1, H))), (None,)
        ),
        "A_log": ini.const(jnp.log(jnp.linspace(1.0, 16.0, H)), (None,)),
        "D": ini.ones((H,), (None,)),
        "norm": ini.zeros((di,), ("ssm_inner",)),
        "out_proj": ini.normal((di, d), ("ssm_inner", "embed")),
    }
    return p


def _split_proj(cfg: ModelConfig, zxbcdt):
    di, N, H = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : 2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N :]
    return z, xbc, dt


def _causal_conv_train(x, w, b):
    """Depthwise causal conv. x: (B, L, C), w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(jnp.float32),
        w[:, None, :].astype(jnp.float32),       # (W, 1, C) HIO
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x.shape[-1],
    )
    return (out + b).astype(x.dtype)


def ssd_chunked(x, dt, A, b, c, chunk: int):
    """Chunked SSD scan.

    x: (B, L, H, P)   dt: (B, L, H)   A: (H,) (negative)
    b, c: (B, L, N)   (ngroups=1, shared across heads)
    Returns (y: (B, L, H, P), final_state: (B, H, N, P)).
    """
    B_, L, H, P = x.shape
    N = b.shape[-1]
    assert L % chunk == 0, f"L={L} not divisible by chunk={chunk}"
    nc = L // chunk
    f32 = jnp.float32

    dtc = dt.reshape(B_, nc, chunk, H).astype(f32)
    bc = b.reshape(B_, nc, chunk, N).astype(f32)
    cc = c.reshape(B_, nc, chunk, N).astype(f32)
    # dt weights the input at s, so it scales x once, not every score
    xdt = x.reshape(B_, nc, chunk, H, P).astype(f32) * dtc[..., None]

    la = dtc * A.astype(f32)                         # log-decay per step
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # inclusive cumsum as a matmul with the lower-triangular ones, so neither
    # pass has a windowed reduction; HIGHEST keeps it f32, since the
    # log-decays reach the hundreds over a chunk
    cs = jnp.einsum("bksh,ts->bkht", la, tri.astype(f32),
                    precision=jax.lax.Precision.HIGHEST)    # (B,nc,H,Q)

    # ---- intra-chunk (quadratic within chunk) ----
    seg = cs[..., :, None] - cs[..., None, :]                 # (B,nc,H,t,s)
    # mask *before* exp so no inf enters the graph (NaN-safe gradients)
    seg = jnp.where(tri, seg, -jnp.inf)
    cb = jnp.einsum("bktn,bksn->bkts", cc, bc)               # (B,nc,t,s)
    scores = cb[:, :, None] * jnp.exp(seg)                   # (B,nc,H,t,s)
    y_intra = jnp.einsum("bkhts,bkshp->bkthp", scores, xdt)

    # ---- chunk boundary states ----
    rem = jnp.exp(cs[..., -1:] - cs)                         # decay to chunk end
    Sk = jnp.einsum("bksn,bkhs,bkshp->bkhnp", bc, rem, xdt)

    chunk_decay = jnp.exp(cs[..., -1])                       # (B,nc,H)

    def scan_fn(h_prev, inp):
        cd, sk = inp                                          # (B,H), (B,H,N,P)
        h = cd[:, :, None, None] * h_prev + sk
        return h, h_prev                                      # emit state *entering* chunk

    h0 = jnp.zeros((B_, H, N, P), f32)
    h_final, h_prevs = jax.lax.scan(
        scan_fn,
        h0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(Sk, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                     # (B,nc,H,N,P)

    # ---- inter-chunk contribution ----
    decay_in = jnp.exp(jnp.swapaxes(cs, 2, 3))                # (B,nc,t,H)
    c_dec = cc[:, :, :, None, :] * decay_in[..., None]        # (B,nc,t,H,N)
    y_inter = jnp.einsum("bkthn,bkhnp->bkthp", c_dec, h_prevs)

    y = (y_intra + y_inter).reshape(B_, L, H, P).astype(x.dtype)
    return y, h_final


def ssd_decode_step(state, x, dt, A, b, c):
    """One-token recurrence.  state: (B,H,N,P); x: (B,H,P); dt: (B,H);
    b, c: (B, N).  Returns (y: (B,H,P), new_state)."""
    f32 = jnp.float32
    a = jnp.exp(dt.astype(f32) * A.astype(f32))               # (B,H)
    outer = jnp.einsum("bn,bh,bhp->bhnp", b.astype(f32), dt.astype(f32),
                       x.astype(f32))
    new_state = a[:, :, None, None] * state + outer
    y = jnp.einsum("bn,bhnp->bhp", c.astype(f32), new_state)
    return y.astype(x.dtype), new_state


class MambaCache(NamedTuple):
    conv: jnp.ndarray     # (B, W-1, conv_channels) — last inputs
    ssd: jnp.ndarray      # (B, H, N, P) fp32 state


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> MambaCache:
    conv_ch = cfg.ssm_inner + 2 * cfg.ssm_state
    return MambaCache(
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch), dtype),
        ssd=jnp.zeros(
            (batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32
        ),
    )


def mamba_block(params, u, cfg: ModelConfig):
    """Full-sequence mamba2 block. u: (B, L, d) -> (y, final MambaCache)."""
    B, L, _ = u.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    zxbcdt = u @ params["in_proj"]
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    xbc_conv = jax.nn.silu(_causal_conv_train(xbc, params["conv_w"], params["conv_b"]))
    x = xbc_conv[..., : cfg.ssm_inner].reshape(B, L, H, P)
    b = xbc_conv[..., cfg.ssm_inner : cfg.ssm_inner + N]
    c = xbc_conv[..., cfg.ssm_inner + N :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    y, h_final = ssd_chunked(x, dt, A, b, c, cfg.ssm_chunk)
    y = y + params["D"].astype(y.dtype)[None, None, :, None] * x
    y = y.reshape(B, L, cfg.ssm_inner)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["norm"])
    out = y @ params["out_proj"]
    # conv cache = last W-1 raw xbc inputs
    W = cfg.ssm_conv_width
    conv_cache = xbc[:, L - (W - 1):, :] if L >= W - 1 else jnp.pad(
        xbc, ((0, 0), (W - 1 - L, 0), (0, 0))
    )
    return out, MambaCache(conv=conv_cache, ssd=h_final)


def mamba_decode(params, u, cache: MambaCache, cfg: ModelConfig):
    """One-token mamba2 step. u: (B, 1, d) -> (y: (B,1,d), new cache)."""
    B = u.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    zxbcdt = u[:, 0] @ params["in_proj"]
    z, xbc, dt = _split_proj(cfg, zxbcdt)

    # causal conv over (cached W-1 inputs, current input)
    hist = jnp.concatenate([cache.conv, xbc[:, None, :]], axis=1)  # (B, W, C)
    conv_out = jnp.einsum(
        "bwc,wc->bc", hist.astype(jnp.float32), params["conv_w"].astype(jnp.float32)
    ) + params["conv_b"].astype(jnp.float32)
    xbc_conv = jax.nn.silu(conv_out).astype(u.dtype)

    x = xbc_conv[..., : cfg.ssm_inner].reshape(B, H, P)
    b = xbc_conv[..., cfg.ssm_inner : cfg.ssm_inner + N]
    c = xbc_conv[..., cfg.ssm_inner + N :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    y, new_ssd = ssd_decode_step(cache.ssd, x, dt, A, b, c)
    y = y + params["D"].astype(y.dtype)[None, :, None] * x
    y = y.reshape(B, cfg.ssm_inner)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, params["norm"])
    out = (y @ params["out_proj"])[:, None, :]
    new_conv = hist[:, 1:, :].astype(cache.conv.dtype)
    return out, MambaCache(conv=new_conv, ssd=new_ssd)
