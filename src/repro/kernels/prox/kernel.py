"""Pallas TPU kernels: proximal operators + fused DEPOSITUM local update.

Elementwise, bandwidth-bound: each grid step streams one VMEM block of every
operand from HBM.  On TPU the fused kernel turns ~7 HBM sweeps of the
unfused update (momentum axpy, shift, prox select chain) into 1 read of
{x, y, nu} + 1 write of {x', nu'}.

Hyperparameters (lam, theta, alpha, gamma, beta) are **runtime scalars**:
they are packed into a tiny SMEM params table rather than baked in as
compile-time constants, so one compiled kernel serves every point of a
hyperparameter sweep.  Only the prox ``kind`` selects code and stays static.

Every kernel here is **sweep-major**: the Pallas grid is
``(n_configs, n_clients, row_tiles, col_tiles)``, the SMEM params block is
an ``(n_configs, 5)`` table indexed by ``pl.program_id(0)``, and an optional
``(n_configs, n_clients)`` SMEM cohort gate freezes masked rows *inside*
the kernel, so a whole stacked-Hyper grid runs as one kernel launch with no
outer ``vmap`` and no per-config retrace.  The single-config entry points
(``prox_pallas`` / ``fused_update_pallas``) are the same grid with
S = C = 1.

Validation split: on CPU everything runs with ``interpret=True`` and is
checked against ``ref.py``; on a TPU the same calls lower through Mosaic
(``tests/test_tpu_compile.py`` compiles them for a described v5e chip,
``chip_smoke.py`` checks them against ``ref.py`` on the chip).  Any other
backend is refused rather than silently interpreted.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from repro import kernels

LANE = 128               # TPU lane width
BLOCK_ELEMS = 128 * 1024  # target elements per operand block (512 KiB f32)
MAX_BLOCK_COLS = 4096    # widest column block (a multiple of LANE)
MIN_BLOCK_ROWS = 32      # multiple of every float sublane tile (f32 8, bf16 16)

# trace-time call counters, keyed by kernel family, and "permuted_view"
# (leaves viewed in a non-row-major device layout, one per leaf and call).
# Incremented inside the jitted wrappers, so a count rises only when XLA
# actually (re)traces — the regression tests pin "zero retraces across
# configs" with these.
TRACE_COUNTS: collections.Counter = collections.Counter()


def reset_trace_counts() -> None:
    TRACE_COUNTS.clear()


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# prox (l1 / mcp / scad), elementwise on a 2-D tile
# ---------------------------------------------------------------------------

def _soft(x, thr):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - thr, 0.0)


def _prox_block(x, kind: str, lam, theta, alpha):
    if kind == "l1":
        return _soft(x, alpha * lam)
    if kind == "mcp":
        a = jnp.abs(x)
        shrunk = _soft(x, alpha * lam) / (1.0 - alpha / theta)
        out = jnp.where(a <= theta * lam, shrunk, x)
        return jnp.where(a <= alpha * lam, jnp.zeros_like(x), out)
    if kind == "scad":
        a = jnp.abs(x)
        r1 = _soft(x, alpha * lam)
        r2 = ((theta - 1.0) * x - jnp.sign(x) * theta * lam * alpha) / (
            theta - 1.0 - alpha
        )
        return jnp.where(a <= (1.0 + alpha) * lam, r1,
                         jnp.where(a <= theta * lam, r2, x))
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Sweep-major layout: the (config, client) axes live IN the grid
# ---------------------------------------------------------------------------
#
# Layout per leaf: (S, C, *param_shape) is viewed as (S, C, R, K) in the
# axis order in which the device already stores the leaf (``view_order``:
# the default layout of the operand's shape on the backend, read once per
# shape and dtype).  K is the leaf's minor dim in that order and R the
# product of its other per-client dims.  The operands are transposed into
# that order before the reshape and the outputs transposed back, so both
# steps only rename the bytes in place (XLA emits bitcasts): no relayout
# copy into the kernel, none out of it, no flat (S, C, d) intermediate
# and no padding copy.  (A flat intermediate of the 50 280 x 768
# embedding stalls the TPU compiler.)  A TPU may store a leaf whose last
# dim is not a multiple of 128 with another dim minor: mamba2-130m's
# in_proj (24, 768, 3352) is kept 768-minor.  The view falls back to
# row-major, K the leaf's own last dim, where the default order is
# row-major (every leaf on the CPU, in interpret mode) or where it does not
# keep (S, C) major: the grid's client axis and the cohort gate need them
# major.  (A rank-1 leaf, stored (C, K) with its clients in the sublanes of
# one tile, is still retiled to a tile per client: C x K elements.)
# Blocks are (1, 1, block_rows, block_cols): a full dim where it
# fits the block budget, else an aligned tile, with the grid rounding up;
# Pallas masks the ragged edge blocks.  The SMEM params table is (S, 5)
# [lam, theta, alpha, gamma, beta] indexed by pl.program_id(0); the
# optional cohort gate is an (S, C) SMEM table indexed by (program_id(0),
# program_id(1)) — masked (config, client) rows are written back
# unchanged inside the kernel, no post-hoc HBM sweep.

# params-table column order (shared with ops.py / depositum.step)
PARAM_COLS = ("lam", "theta", "alpha", "gamma", "beta")


class SweepLayout(NamedTuple):
    """Static (R, K) block layout of one leaf's per-client parameters."""

    rows: int        # R: product of the per-client dims but the last
    cols: int        # K: the per-client last dim
    block_rows: int  # rows per grid step (R itself, or a power of two)
    block_cols: int  # cols per grid step (K itself, or MAX_BLOCK_COLS)

    @property
    def size(self) -> int:
        return self.rows * self.cols

    @property
    def tiles(self) -> tuple[int, int]:
        """(row tiles, col tiles) of the grid; edge tiles may be ragged."""
        return (pl.cdiv(self.rows, self.block_rows),
                pl.cdiv(self.cols, self.block_cols))


@functools.lru_cache(maxsize=None)
def sweep_layout(shape: tuple[int, ...]) -> SweepLayout:
    """Block layout for a per-client leaf of ``shape``, computed once per
    distinct shape (the fused path does no host-side shape arithmetic per
    round)."""
    cols = shape[-1] if shape else 1
    rows = math.prod(shape[:-1])
    block_cols = min(cols, MAX_BLOCK_COLS)
    lanes = pl.cdiv(block_cols, LANE) * LANE
    cap = max(MIN_BLOCK_ROWS, 1 << ((BLOCK_ELEMS // lanes).bit_length() - 1))
    return SweepLayout(rows=rows, cols=cols,
                       block_rows=rows if rows <= cap else cap,
                       block_cols=block_cols)


def sweep_params_table(lam, theta, alpha, gamma, beta=0.0) -> jnp.ndarray:
    """(S, 5) fp32 params table from scalars or stacked (S,) leaves."""
    cols = [jnp.asarray(v, jnp.float32) for v in (lam, theta, alpha, gamma,
                                                  beta)]
    S = max((int(c.shape[0]) for c in cols if c.ndim == 1), default=1)
    cols = [jnp.broadcast_to(c.reshape(-1), (S,)) for c in cols]
    return jnp.stack(cols, axis=-1)


def _prox_sweep_kernel(p_ref, x_ref, o_ref, *, kind):
    """prox_{alpha h}(x), one VMEM pass per (s, c, tile)."""
    s = pl.program_id(0)
    lam, theta, alpha = p_ref[s, 0], p_ref[s, 1], p_ref[s, 2]
    x = x_ref[0, 0].astype(jnp.float32)
    o_ref[0, 0] = _prox_block(x, kind, lam, theta, alpha).astype(o_ref.dtype)


def _fused_sweep_kernel(p_ref, *refs, kind, gated):
    """Momentum + tracking shift + prox, one VMEM pass per (s, c, tile):

        nu' = gamma nu + (1 - gamma) y
        x'  = prox_{alpha h}(x - alpha nu')        (kind in l1 | mcp | scad)

    with the config's hyperparameters read from the SMEM table row
    ``program_id(0)`` and — when ``gated`` — frozen (config, client) rows
    written back unchanged via the SMEM cohort gate."""
    s = pl.program_id(0)
    if gated:
        m_ref, x_ref, y_ref, nu_ref, xo_ref, nuo_ref = refs
    else:
        x_ref, y_ref, nu_ref, xo_ref, nuo_ref = refs
    lam, theta = p_ref[s, 0], p_ref[s, 1]
    alpha, gamma = p_ref[s, 2], p_ref[s, 3]
    x = x_ref[0, 0].astype(jnp.float32)
    y = y_ref[0, 0].astype(jnp.float32)
    nu = nu_ref[0, 0].astype(jnp.float32)
    nu_next = gamma * nu + (1.0 - gamma) * y
    x_next = _prox_block(x - alpha * nu_next, kind, lam, theta, alpha)
    if gated:
        live = m_ref[s, pl.program_id(1)] > 0
        x_next = jnp.where(live, x_next, x)
        nu_next = jnp.where(live, nu_next, nu)
    xo_ref[0, 0] = x_next.astype(xo_ref.dtype)
    nuo_ref[0, 0] = nu_next.astype(nuo_ref.dtype)


def _tracking_sweep_kernel(p_ref, *refs, gated):
    """Gradient-tracking axpy, one VMEM pass per (s, c, tile):

        y' = y + beta (g_new - g_old)

    When ``gated`` the kernel also emits the kept gradient
    ``g' = where(live, g_new, g_old)`` so the round program's freeze of
    frozen rows costs no extra sweep."""
    s = pl.program_id(0)
    if gated:
        m_ref, y_ref, gn_ref, go_ref, yo_ref, gk_ref = refs
    else:
        y_ref, gn_ref, go_ref, yo_ref = refs
    beta = p_ref[s, 4]
    y = y_ref[0, 0].astype(jnp.float32)
    gn = gn_ref[0, 0].astype(jnp.float32)
    go = go_ref[0, 0].astype(jnp.float32)
    y_next = y + beta * (gn - go)
    if gated:
        live = m_ref[s, pl.program_id(1)] > 0
        y_next = jnp.where(live, y_next, y)
        gk_ref[0, 0] = jnp.where(live, gn, go).astype(gk_ref.dtype)
    yo_ref[0, 0] = y_next.astype(yo_ref.dtype)


@functools.lru_cache(maxsize=None)
def _default_order(shape: tuple[int, ...], dtype: np.dtype,
                   device) -> tuple[int, ...]:
    """Major-to-minor axis order of ``device``'s default layout for an
    array of ``shape`` and ``dtype``, if it keeps axes 0 and 1 (S, C)
    major; else the row-major order."""
    pjrt = device.client.get_default_layout(dtype, shape, device)
    order = tuple(Layout.from_pjrt_layout(pjrt).major_to_minor)
    return order if order[:2] == (0, 1) else tuple(range(len(shape)))


def view_order(shape: tuple[int, ...], dtype) -> tuple[int, ...]:
    """Axis order in which the kernels view an (S, C, *p) operand: the
    order the device stores it in under Mosaic, row-major in interpret
    mode (see the layout notes above)."""
    if kernels.interpret_mode():
        return tuple(range(len(shape)))
    return _default_order(tuple(shape), np.dtype(dtype),
                          kernels.layout_device())


def _grid_call_local(kernel, out_dtypes, params, mask, x, *operands):
    """One pallas_call over (S, C, *p) leaves held by one device."""
    S, C = x.shape[:2]
    order = view_order(x.shape, x.dtype)
    permuted = order != tuple(range(x.ndim))
    leaves = (x,) + operands
    if permuted:
        TRACE_COUNTS["permuted_view"] += 1
        leaves = tuple(jnp.transpose(a, order) for a in leaves)
    shape = leaves[0].shape
    lay = sweep_layout(tuple(shape[2:]))
    views = [a.reshape(S, C, lay.rows, lay.cols) for a in leaves]
    bs = pl.BlockSpec((1, 1, lay.block_rows, lay.block_cols),
                      lambda s, c, i, j: (s, c, i, j))
    smem = [_scalar_spec()]
    ins = [params]
    if mask is not None:
        smem.append(_scalar_spec())
        ins.append(mask)
    outs = pl.pallas_call(
        kernel,
        grid=(S, C) + lay.tiles,
        in_specs=smem + [bs] * len(views),
        out_specs=[bs] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct(views[0].shape, dt)
                   for dt in out_dtypes],
        interpret=kernels.interpret_mode(),
    )(*ins, *views)
    outs = tuple(o.reshape(shape) for o in outs)
    if permuted:
        inverse = tuple(order.index(i) for i in range(len(order)))
        outs = tuple(jnp.transpose(o, inverse) for o in outs)
    return outs


class ClientShards(NamedTuple):
    """Where the client dim of the kernel operands is split: over
    ``axis_name`` of ``mesh``, one block of clients per device (the
    shard_map backend's placement)."""

    mesh: Mesh
    axis_name: str | tuple[str, ...]


def _sweep_grid_call(kernel, out_dtypes, x, *operands, params, mask,
                     shards: ClientShards | None = None):
    """Shared pallas_call plumbing for the sweep-major kernels.

    ``x`` and ``operands`` are (S, C, *p) leaves (same shape); ``params`` is
    the (S, 5) table, ``mask`` an optional (S, C) gate.  Returns one
    (S, C, *p) output per entry of ``out_dtypes``.

    A Mosaic kernel cannot be partitioned by XLA, so on a multi-device
    program ``shards`` names the split of the client dim and the kernel
    runs under ``shard_map``: every device updates its own client rows,
    with the params table replicated and the gate split like the clients,
    and nothing crosses devices.
    """
    gated = mask is not None
    params = jnp.asarray(params, jnp.float32)
    mask = jnp.asarray(mask, jnp.float32) if gated else None
    call = functools.partial(_grid_call_local, kernel, out_dtypes)
    if shards is None:
        return call(params, mask, x, *operands)
    clients = P(None, shards.axis_name)
    tables = (P(),) + ((clients,) if gated else ())
    local = (call if gated else
             lambda p, *leaves: call(p, None, *leaves))
    return jax.shard_map(
        local, mesh=shards.mesh,
        in_specs=tables + (clients,) * (1 + len(operands)),
        out_specs=(clients,) * len(out_dtypes),
        # the Pallas interpreter (CPU) cannot type varying mesh axes
        check_vma=False,
    )(*((params, mask) if gated else (params,)), x, *operands)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("kind",))
def prox_pallas(x, *, kind: str = "l1", lam=1e-4, theta=4.0, alpha=0.1):
    """prox_{alpha*h}(x) for separable h; any shape/dtype; tiled VMEM pass.

    ``lam``/``theta``/``alpha`` may be Python floats or traced jnp scalars;
    either way they ride in SMEM and do not trigger recompilation.
    """
    TRACE_COUNTS["prox"] += 1
    (out,) = _sweep_grid_call(
        functools.partial(_prox_sweep_kernel, kind=kind), (x.dtype,),
        x[None, None], params=sweep_params_table(lam, theta, alpha, 0.0),
        mask=None)
    return out[0, 0]


@functools.partial(jax.jit, static_argnames=("kind",))
def fused_update_pallas(x, y, nu, *, kind: str = "l1", lam=1e-4,
                        theta=4.0, alpha=0.1, gamma=0.8):
    """Fused momentum+prox (one VMEM pass).  Returns (x', nu').

    Hyperparameters are runtime SMEM scalars — sweep-safe, recompile-free.
    """
    TRACE_COUNTS["fused_update"] += 1
    assert x.shape == y.shape == nu.shape
    one = lambda a: a[None, None]
    xo, nuo = _sweep_grid_call(
        functools.partial(_fused_sweep_kernel, kind=kind, gated=False),
        (x.dtype, nu.dtype), one(x), one(y), one(nu),
        params=sweep_params_table(lam, theta, alpha, gamma), mask=None)
    return xo[0, 0], nuo[0, 0]


@functools.partial(jax.jit, static_argnames=("kind", "shards"))
def fused_update_sweep_pallas(x, y, nu, params, mask=None, *,
                              kind: str = "l1",
                              shards: ClientShards | None = None):
    """Sweep-major fused momentum+prox update.  Returns (x', nu').

    ``x``/``y``/``nu``: (S, C, *param_shape) — S stacked configs, C clients;
    ``params``: (S, 5) runtime table (:func:`sweep_params_table`), ``mask``:
    optional (S, C) cohort gate (0 rows come back bit-identical).  One
    compiled kernel serves every config of the grid: the table rides in
    SMEM, so new hyperparameter values never retrace.  ``shards`` splits
    the client dim over devices (:class:`ClientShards`).
    """
    TRACE_COUNTS["fused_sweep"] += 1
    assert x.shape == y.shape == nu.shape and x.ndim >= 2
    kernel = functools.partial(_fused_sweep_kernel, kind=kind,
                               gated=mask is not None)
    xo, nuo = _sweep_grid_call(kernel, (x.dtype, nu.dtype), x, y, nu,
                               params=params, mask=mask, shards=shards)
    return xo, nuo


@functools.partial(jax.jit, static_argnames=("shards",))
def fused_tracking_sweep_pallas(y, g_new, g_old, params, mask=None, *,
                                shards: ClientShards | None = None):
    """Sweep-major tracking axpy.  Returns (y', g_kept).

    Same layout contract as :func:`fused_update_sweep_pallas`; ``beta``
    comes from column 4 of the params table.  Without a mask ``g_kept`` is
    ``g_new`` itself (no copy)."""
    TRACE_COUNTS["tracking_sweep"] += 1
    assert y.shape == g_new.shape == g_old.shape and y.ndim >= 2
    gated = mask is not None
    kernel = functools.partial(_tracking_sweep_kernel, gated=gated)
    dts = (y.dtype, g_new.dtype) if gated else (y.dtype,)
    outs = _sweep_grid_call(kernel, dts, y, g_new, g_old,
                            params=params, mask=mask, shards=shards)
    return outs[0], (outs[1] if gated else g_new)
