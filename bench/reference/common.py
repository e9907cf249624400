"""Plain float32 building blocks of the reference models.

Everything here is straightforward ``jax.numpy``: no kernels, no caches, no
chunked scans.  Matrix products run at ``precision="highest"`` (true float32
on a TPU).  A :class:`Numerics` object says how operands are rounded
before each product and in which precision the run holds its weights and
optimizer state between updates:

* :data:`REFERENCE` computes in float32 and holds the state in the
  configuration's dtype, rounding to nearest after every update, as the
  configuration states (bf16 weights, no float32 master copy);
* :data:`FLOAT32` holds the state in float32 as well (for the record: with
  bf16 weights most of a step's updates are below half an ulp, so its
  weights move where the configuration's cannot);
* :data:`CONTROL` rounds every matmul operand and the state to float8 e4m3
  with one scale per tensor: one precision below bfloat16.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# float8 e4m3 as an IEEE-style format (4 exponent, 3 mantissa bits; largest
# finite 240).  Rounding goes through ``lax.reduce_precision``, which the
# compiler keeps: a round trip through a narrower dtype
# (``astype(bf16).astype(f32)``) may be removed as excess precision.
F8_BITS = (4, 3)
#: time steps of the recurrence per loop iteration
SCAN_UNROLL = 4
F8_MAX = 240.0


def round_to(t, exponent_bits: int, mantissa_bits: int):
    return jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits,
                                    mantissa_bits)


def round_fp8(t):
    """Round to float8 e4m3 with one scale per tensor (amax -> the largest
    finite value); the gradient passes straight through."""
    t = t.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(t)))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = round_to(t / scale, *F8_BITS) * scale
    return t + jax.lax.stop_gradient(q - t)


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference rounds matmul operands and stored state."""

    name: str
    fp8_operands: bool = False
    store: str = "config"     # "config" | "float32" | "fp8"

    def cast(self, t):
        return round_fp8(t) if self.fp8_operands else t.astype(jnp.float32)

    def mm(self, spec: str, a, b):
        """einsum of two operands, each rounded by this policy first."""
        return jnp.einsum(spec, self.cast(a), self.cast(b), precision=HIGHEST)

    def storer(self, dtype):
        """t -> t as held between updates (float32 values)."""
        if self.store == "fp8":
            return round_fp8
        if self.store == "float32" or dtype == jnp.float32:
            return lambda t: t.astype(jnp.float32)
        fi = jnp.finfo(dtype)
        return lambda t: round_to(t, fi.nexp, fi.nmant)


REFERENCE = Numerics("reference")
FLOAT32 = Numerics("float32", store="float32")
CONTROL = Numerics("control", fp8_operands=True, store="fp8")
NUMERICS = {n.name: n for n in (REFERENCE, FLOAT32, CONTROL)}


def rms_norm(x, w, eps: float = 1e-6):
    """x / rms(x) * (1 + w): the scale is stored zero-centred."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_depthwise_conv(x, w, b):
    """x (B, L, C), w (W, C), b (C,): out_t = b + sum_k w_k x_{t-W+1+k}."""
    W, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return b + sum(xp[:, k:k + L, :] * w[k] for k in range(W))


def ssd_sequential(x, dt, A, b, c):
    """The selective-state recurrence, one token at a time.

        h_t = exp(dt_t A) h_{t-1} + dt_t b_t x_t^T     h: (N, P) per head
        y_t = c_t h_t

    x (B, L, H, P), dt (B, L, H), A (H,), b and c (B, L, N) shared by all
    heads (one group).  Returns y (B, L, H, P).
    """
    Bn, L, H, P = x.shape
    N = b.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * A)                                  # (B, H)
        inject = jnp.einsum("bh,bn,bhp->bhnp", dtt, bt, xt,
                            precision=HIGHEST)
        h = decay[:, :, None, None] * h + inject
        y = jnp.einsum("bn,bhnp->bhp", ct, h, precision=HIGHEST)
        return h, y

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    h0 = jnp.zeros((Bn, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (seq(x), seq(dt), seq(b), seq(c)),
                         unroll=SCAN_UNROLL)
    return jnp.moveaxis(ys, 0, 1)


def mamba2_block(p, u, cfg: dict, num: Numerics):
    """Mamba-2 mixer on u (B, L, d): fused in-projection [z, x, B, C, dt],
    causal depthwise conv + SiLU on [x, B, C], the selective recurrence,
    skip D, gate SiLU(z), gated RMS norm, out-projection."""
    Bn, L, _ = u.shape
    di = cfg["ssm_expand"] * cfg["d_model"]
    N, P = cfg["ssm_state"], cfg["ssm_head_dim"]
    H = di // P
    zxbcdt = num.mm("bld,de->ble", u, p["in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N:]
    xbc = silu(causal_depthwise_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :di].reshape(Bn, L, H, P)
    bm = xbc[..., di:di + N]
    cm = xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = ssd_sequential(x, dt, A, bm, cm)
    y = y + p["D"][None, None, :, None] * x
    y = y.reshape(Bn, L, di) * silu(z)
    y = rms_norm(y, p["norm"])
    return num.mm("ble,ed->bld", y, p["out_proj"])


def rope(x, theta: float):
    """Rotary embedding, halves convention: x (B, L, H, hd)."""
    L, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs       # (L, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(p, x, cfg: dict, num: Numerics):
    """Multi-head causal self-attention with RoPE, full context."""
    Bn, L, d = x.shape
    H = cfg["n_heads"]
    hd = d // H
    q = num.mm("bld,de->ble", x, p["wq"]).reshape(Bn, L, H, hd)
    k = num.mm("bld,de->ble", x, p["wk"]).reshape(Bn, L, H, hd)
    v = num.mm("bld,de->ble", x, p["wv"]).reshape(Bn, L, H, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    s = num.mm("blhe,bshe->bhls", q, k) / jnp.sqrt(jnp.float32(hd))
    mask = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = num.mm("bhls,bshe->blhe", a, v).reshape(Bn, L, d)
    return num.mm("ble,ed->bld", o, p["wo"])


def swiglu(p, x, num: Numerics):
    h = silu(num.mm("bld,df->blf", x, p["w_gate"])) * num.mm(
        "bld,df->blf", x, p["w_up"])
    return num.mm("blf,fd->bld", h, p["w_down"])


def cross_entropy(logits, labels):
    """Mean over every token of -log softmax(logits)[label]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m
