"""Pallas TPU kernel: the dense client mix ``out_i = sum_j W_ij x_j``, in
the state's own layout.

XLA lowers the dense mix's ``einsum`` over the client dim to a
``convolution`` whose output it lays out with the clients interleaved in
the minor tiles, then copies it back into the state's layout: the mix
moves every byte of x and y twice.  This kernel reads each leaf once and
writes it once, in the layout the device stores it in.

Each (S, C, *p) leaf is viewed like the update kernels' operands
(``prox.kernel.view_order`` / ``sweep_layout``): transposed into the
device's axis order, reshaped to (S, C, R, K), both of which XLA makes
bitcasts.  Unlike the update kernels, whose grid steps hold one client,
each grid step here holds **all C clients** of the same positions, a
``(1, C, block_rows, block_cols)`` block, and writes the mixed block in
the same view.  The sum runs in float32 and is rounded once to the
leaf's dtype.  W rides in SMEM as an (S, C * C) float32 table indexed by
``pl.program_id(0)``, so a traced W (schedules, cohorts, stacked sweep
plans) never retraces, and under ``jax.vmap`` over a stacked plan the
sweep axis becomes grid axis 0.

:func:`dense_mix` is ``core.mixing.apply_mix``'s dense arm for one leaf.
It runs the kernel where Mosaic compiles it and the leaf is eligible
(:func:`mix_layout`), and the ``einsum`` otherwise: on the CPU, where the
einsum is the path and the kernel runs only in interpret mode in its own
tests, and for a leaf split over several devices (a Mosaic kernel cannot
be partitioned by XLA).  W is cast to the leaf's dtype first on both
paths, so a W exact in that dtype mixes to the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.prox.kernel import (
    BLOCK_ELEMS,
    LANE,
    TRACE_COUNTS,
    SweepLayout,
    device_order,
    sweep_layout,
)

#: VMEM the fused update kernel's blocks take at most: 3 inputs and 2
#: outputs of BLOCK_ELEMS float32 elements, each double-buffered (5 MiB).
#: A C-client block of the mix, in and out, double-buffered, fits it.
VMEM_BUDGET = 2 * 5 * BLOCK_ELEMS * 4


def _block_bytes(lay: SweepLayout, clients: int, itemsize: int) -> int:
    """VMEM of one grid step's in and out blocks, double-buffered, with
    rows padded to the dtype's sublane tile and columns to the lanes."""
    sublanes = 32 // itemsize
    rows = pl.cdiv(lay.block_rows, sublanes) * sublanes
    cols = pl.cdiv(lay.block_cols, LANE) * LANE
    return 2 * 2 * clients * rows * cols * itemsize


@functools.lru_cache(maxsize=None)
def _fitted_layout(shape: tuple[int, ...], clients: int,
                   itemsize: int) -> SweepLayout | None:
    lay = sweep_layout(shape, VMEM_BUDGET // (4 * clients * itemsize))
    if _block_bytes(lay, clients, itemsize) > VMEM_BUDGET:
        return None
    return lay


def mix_layout(shape: tuple[int, ...], dtype) -> SweepLayout | None:
    """Block layout of the kernel's view of an (S, C, *p) leaf, or None
    where the kernel cannot take it: a dtype other than a 16- or 32-bit
    float, a device layout that does not keep (S, C) major, or a block of
    all C clients that does not fit :data:`VMEM_BUDGET` even at the
    fewest rows a block may have.  The view's per-client dims are the
    leaf's in the device's axis order."""
    dtype = np.dtype(dtype)
    if not (jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize in (2, 4)):
        return None
    order = device_order(tuple(shape), dtype)
    if order[:2] != (0, 1):
        return None
    per_client = tuple(shape[a] for a in order[2:])
    return _fitted_layout(per_client, int(shape[1]), dtype.itemsize)


def _mix_kernel(w_ref, x_ref, o_ref):
    """out_i = sum_j W[s, i, j] x_j over the block's C clients, in f32."""
    s = pl.program_id(0)
    C = x_ref.shape[1]
    xs = [x_ref[0, j].astype(jnp.float32) for j in range(C)]
    for i in range(C):
        acc = w_ref[s, i * C] * xs[0]
        for j in range(1, C):
            acc = acc + w_ref[s, i * C + j] * xs[j]
        o_ref[0, i] = acc.astype(o_ref.dtype)


@jax.jit
def dense_mix_pallas(W, x):
    """Mix (S, C, *p) ``x`` over its client dim with the (S, C, C) ``W``
    (already in ``x``'s dtype)."""
    lay = mix_layout(x.shape, x.dtype)
    if lay is None:
        raise ValueError(f"the mix kernel cannot take a {x.dtype} leaf of "
                         f"shape {x.shape} (see mix_layout)")
    S, C = x.shape[:2]
    order = device_order(x.shape, x.dtype)
    view = jnp.transpose(x, order).reshape(S, C, lay.rows, lay.cols)
    table = W.astype(jnp.float32).reshape(S, C * C)
    bs = pl.BlockSpec((1, C, lay.block_rows, lay.block_cols),
                      lambda s, i, j: (s, 0, i, j))
    out = pl.pallas_call(
        _mix_kernel,
        grid=(S,) + lay.tiles,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), bs],
        out_specs=bs,
        out_shape=jax.ShapeDtypeStruct(view.shape, x.dtype),
        interpret=kernels.interpret_mode(),
    )(table, view)
    inverse = tuple(order.index(a) for a in range(x.ndim))
    shape = tuple(x.shape[a] for a in order)
    return jnp.transpose(out.reshape(shape), inverse)


@jax.custom_batching.custom_vmap
def _mix_one(W, x):
    return dense_mix_pallas(W[None], x[None])[0]


@_mix_one.def_vmap
def _mix_rule(axis_size, in_batched, W, x):
    """A stacked plan's sweep axis is the kernel's grid axis 0."""
    w_b, x_b = in_batched
    W = W if w_b else jnp.broadcast_to(W[None], (axis_size,) + W.shape)
    x = x if x_b else jnp.broadcast_to(x[None], (axis_size,) + x.shape)
    return dense_mix_pallas(W, x), True


def _kernel_takes(x) -> bool:
    """Whether the kernel mixes the (C, *p) leaf ``x``: under Mosaic, where
    :func:`mix_layout` takes it and it is held by one device."""
    return (not kernels.interpret_mode()
            and mix_layout((1,) + x.shape, x.dtype) is not None
            and jax.typeof(x).sharding.mesh.size <= 1)


def dense_mix(W, x):
    """``einsum("ij,j...->i...", W, x)`` for one (C, *p) leaf, with W cast
    to the leaf's dtype: by the kernel where it takes the leaf, else by
    the einsum.  Each trace counts the leaf under Mosaic:
    ``TRACE_COUNTS["mix"]`` where the kernel mixes it,
    ``TRACE_COUNTS["mix_fallback"]`` where the einsum does."""
    W = W.astype(x.dtype)
    if _kernel_takes(x):
        TRACE_COUNTS["mix"] += 1
        return _mix_one(W, x)
    if not kernels.interpret_mode():
        TRACE_COUNTS["mix_fallback"] += 1
    return jnp.einsum("ij,j...->i...", W, x,
                      precision=jax.lax.Precision.HIGHEST)
