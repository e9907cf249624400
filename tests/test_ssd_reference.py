"""The chunked SSD scan against the plain float32 recurrence.

``ssd_chunked`` builds each chunk's decay from a cumulative log-decay
(a matmul with the lower-triangular ones) and weights the input by dt
before the intra-chunk and chunk-state contractions.  This checks it, in
forward and in the gradients of every input that carries a gradient in
training, against the token-by-token recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t b_t x_t^T,     y_t = c_t h_t

at mamba2-130m widths (24 heads of 64, state 128, chunk 256) over two
chunks, with dt and A drawn over the published init ranges, so that a
chunk's log-decay reaches the hundreds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.mamba2 import ssd_chunked

B, L, H, P, N, Q = 1, 512, 24, 64, 128, 256
#: published init ranges: dt in [dt_min, dt_max], A = -[1, 16]
DT_MIN, DT_MAX = 1e-3, 0.1
#: max abs error over the reference's max abs value; the two orders of
#: summation meet within a few 1e-6 in float32
RTOL = 1e-4


def ssd_recurrence(x, dt, A, b, c):
    """h_t = exp(dt_t A) h_{t-1} + dt_t b_t x_t^T, y_t = c_t h_t, one token
    at a time.  Returns (y (B, L, H, P), final h (B, H, N, P))."""

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * A)[:, :, None, None] * h
             + jnp.einsum("bh,bn,bhp->bhnp", dtt, bt, xt))
        return h, jnp.einsum("bn,bhnp->bhp", ct, h)

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    h0 = jnp.zeros((x.shape[0], H, N, P), jnp.float32)
    h, ys = jax.lax.scan(step, h0, (seq(x), seq(dt), seq(b), seq(c)))
    return jnp.moveaxis(ys, 0, 1), h


@pytest.fixture(scope="module")
def outputs():
    """(y, final state, dx, ddt, db, dc) of the chunked scan and of the
    recurrence, on the same inputs and the same cotangents."""
    kx, kdt, kb, kc, ky, kh = jax.random.split(jax.random.key(17), 6)
    x = jax.random.normal(kx, (B, L, H, P), jnp.float32)
    dt = jax.random.uniform(kdt, (B, L, H), jnp.float32, DT_MIN, DT_MAX)
    A = -jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)
    b = jax.random.normal(kb, (B, L, N), jnp.float32)
    c = jax.random.normal(kc, (B, L, N), jnp.float32)
    wy = jax.random.normal(ky, (B, L, H, P), jnp.float32)
    wh = jax.random.normal(kh, (B, H, N, P), jnp.float32)

    chunk_end = (dt * A).reshape(B, L // Q, Q, H).sum(axis=2)
    assert float(chunk_end.min()) < -100.0

    def run(scan):
        def loss(x, dt, b, c):
            y, h = scan(x, dt, A, b, c)
            return jnp.sum(y * wy) + jnp.sum(h * wh)

        y, h = jax.jit(scan)(x, dt, A, b, c)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, dt, b, c)
        return (y, h) + tuple(grads)

    chunked = run(lambda *a: ssd_chunked(*a, chunk=Q))
    return chunked, run(ssd_recurrence)


NAMES = ["y", "final_state", "grad_x", "grad_dt", "grad_b", "grad_c"]


@pytest.mark.parametrize("index", range(len(NAMES)), ids=NAMES)
def test_chunked_ssd_matches_recurrence(outputs, index):
    got, want = (np.asarray(o[index]) for o in outputs)
    assert got.shape == want.shape
    scale = np.max(np.abs(want))
    assert scale > 0
    err = np.max(np.abs(got - want)) / scale
    assert err < RTOL, f"{NAMES[index]}: {err:.2e} of its max"
