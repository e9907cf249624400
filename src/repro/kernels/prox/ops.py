"""Jit'd public wrappers for the prox kernels (pytree-aware).

All hyperparameters (``lam``/``theta``/``alpha``/``gamma``/``beta``) may be
Python floats **or traced jnp scalars** — they are forwarded to the kernels
as runtime SMEM operands, so sweeping them never recompiles.

Two entry levels:

* tree wrappers (``prox_tree`` / ``fused_update_tree`` /
  ``fused_update_sweep_tree`` / ``fused_tracking_sweep_tree``) apply a
  kernel leafwise; the sweep variants expect explicit (S, C, ...) leaves.
* :func:`fused_local_update` / :func:`fused_tracking` are the round
  program's entry points: ``jax.custom_batching.custom_vmap`` functions
  whose *unbatched* call runs the sweep-major kernel with a single-config
  axis (S = 1) and whose **vmap rule maps the stacked-Hyper sweep axis onto
  Pallas grid axis 0** — so ``jax.vmap``-ing a whole federated run over
  stacked configs (``repro.training.sweep``) executes ONE sweep-major
  kernel launch per leaf instead of S per-config launches, with zero
  retraces across configs.

Every entry reaches the kernels with (S, C, *param_shape) leaves, which the
kernels view in the axis order the backend's default layout stores them in
(``kernel.view_order``): under Mosaic on a TPU, mamba2-130m's in_proj
(24, 768, 3352) is read 768-minor as the chip keeps it, so no relayout copy
goes into or out of the kernel.  The view is row-major where that layout is
(every leaf on the CPU, in interpret mode) or where it would not keep the
(S, C) axes major.  Element order changes nothing of an elementwise
update, so results do not depend on the view.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs.trace import annotate
from repro.kernels.prox.kernel import (
    ClientShards,
    fused_tracking_sweep_pallas,
    fused_update_pallas,
    fused_update_sweep_pallas,
    prox_pallas,
    sweep_params_table,
)

tm = jax.tree_util.tree_map


def prox_tree(tree, *, kind: str, lam, alpha, theta=4.0):
    """Apply the Pallas prox leafwise over a parameter pytree."""
    return jax.tree_util.tree_map(
        lambda leaf: prox_pallas(leaf, kind=kind, lam=lam, theta=theta,
                                 alpha=alpha),
        tree,
    )


def fused_update_tree(x_tree, y_tree, nu_tree, *, kind: str, lam,
                      alpha, gamma, theta=4.0):
    """Fused DEPOSITUM local update over pytrees.  Returns (x', nu')."""
    flat_x, treedef = jax.tree_util.tree_flatten(x_tree)
    flat_y = treedef.flatten_up_to(y_tree)
    flat_nu = treedef.flatten_up_to(nu_tree)
    outs = [
        fused_update_pallas(x, y, nu, kind=kind, lam=lam, theta=theta,
                            alpha=alpha, gamma=gamma)
        for x, y, nu in zip(flat_x, flat_y, flat_nu)
    ]
    xs = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    nus = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return xs, nus


# ---------------------------------------------------------------------------
# Sweep-major: explicit (S, C, ...) leaves
# ---------------------------------------------------------------------------

def fused_update_sweep_tree(x_tree, y_tree, nu_tree, params, mask=None, *,
                            kind: str, shards: ClientShards | None = None):
    """Sweep-major fused update over pytrees of (S, C, ...) leaves.

    ``params`` is the (S, 5) table (:func:`~repro.kernels.prox.kernel.
    sweep_params_table`); ``mask`` an optional (S, C) cohort gate;
    ``shards`` the devices' split of the client dim.  Returns (x', nu').
    """
    flat_x, treedef = jax.tree_util.tree_flatten(x_tree)
    flat_y = treedef.flatten_up_to(y_tree)
    flat_nu = treedef.flatten_up_to(nu_tree)
    outs = [
        fused_update_sweep_pallas(x, y, nu, params, mask, kind=kind,
                                  shards=shards)
        for x, y, nu in zip(flat_x, flat_y, flat_nu)
    ]
    xs = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    nus = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return xs, nus


def fused_tracking_sweep_tree(y_tree, g_new_tree, g_old_tree, params,
                              mask=None, *, shards: ClientShards | None = None):
    """Sweep-major tracking axpy over pytrees.  Returns (y', g_kept)."""
    flat_y, treedef = jax.tree_util.tree_flatten(y_tree)
    flat_gn = treedef.flatten_up_to(g_new_tree)
    flat_go = treedef.flatten_up_to(g_old_tree)
    outs = [
        fused_tracking_sweep_pallas(y, gn, go, params, mask, shards=shards)
        for y, gn, go in zip(flat_y, flat_gn, flat_go)
    ]
    ys = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    gs = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return ys, gs


# ---------------------------------------------------------------------------
# custom_vmap entry points: the sweep axis becomes grid axis 0, not a vmap
# ---------------------------------------------------------------------------

def _broadcast_unbatched(axis_size, tree, batched):
    """Give every unbatched leaf the (axis_size,) sweep dim batched leaves
    already carry (XLA materialises the broadcast lazily)."""
    return tm(
        lambda leaf, b: leaf if b else jnp.broadcast_to(
            leaf[None], (axis_size,) + jnp.shape(leaf)),
        tree, batched)


@functools.lru_cache(maxsize=None)
def _make_fused_local_update(kind: str, gated: bool,
                             shards: ClientShards | None):
    """Build the custom_vmap'd local-update entry for one prox ``kind``.

    The unbatched call adds a singleton config axis and runs the sweep
    kernel with S = 1 (grid (1, C, tiles)); under ``jax.vmap`` over stacked
    configs the rule below maps the batch axis straight onto grid axis 0 —
    one kernel launch for the whole grid, hyperparameters in the SMEM
    table, no outer vmap of S separate kernels.
    """

    def impl(x, y, nu, hp_vec, mask):
        one = lambda tree: tm(lambda l: l[None], tree)
        m1 = mask[None] if gated else None
        xs, nus = fused_update_sweep_tree(
            one(x), one(y), one(nu), hp_vec[None], m1, kind=kind,
            shards=shards)
        drop = lambda tree: tm(lambda l: l[0], tree)
        return drop(xs), drop(nus)

    if gated:
        f = jax.custom_batching.custom_vmap(impl)
    else:
        f = jax.custom_batching.custom_vmap(
            lambda x, y, nu, hp_vec: impl(x, y, nu, hp_vec, None))

    @f.def_vmap
    def _rule(axis_size, in_batched, x, y, nu, hp_vec, *rest):
        xb = _broadcast_unbatched(axis_size, x, in_batched[0])
        yb = _broadcast_unbatched(axis_size, y, in_batched[1])
        nub = _broadcast_unbatched(axis_size, nu, in_batched[2])
        hpb = hp_vec if in_batched[3] else jnp.broadcast_to(
            hp_vec[None], (axis_size,) + hp_vec.shape)
        mb = None
        if gated:
            (mask,) = rest
            mb = mask if in_batched[4] else jnp.broadcast_to(
                mask[None], (axis_size,) + mask.shape)
        out = fused_update_sweep_tree(xb, yb, nub, hpb, mb, kind=kind,
                                      shards=shards)
        return out, tm(lambda _: True, out)

    return f


@functools.lru_cache(maxsize=None)
def _make_fused_tracking(gated: bool, shards: ClientShards | None):
    """custom_vmap'd tracking entry (same dispatch as the update)."""

    def impl(y, g_new, g_old, hp_vec, mask):
        one = lambda tree: tm(lambda l: l[None], tree)
        m1 = mask[None] if gated else None
        ys, gs = fused_tracking_sweep_tree(
            one(y), one(g_new), one(g_old), hp_vec[None], m1, shards=shards)
        drop = lambda tree: tm(lambda l: l[0], tree)
        return drop(ys), drop(gs)

    if gated:
        f = jax.custom_batching.custom_vmap(impl)
    else:
        f = jax.custom_batching.custom_vmap(
            lambda y, g_new, g_old, hp_vec: impl(y, g_new, g_old, hp_vec,
                                                 None))

    @f.def_vmap
    def _rule(axis_size, in_batched, y, g_new, g_old, hp_vec, *rest):
        yb = _broadcast_unbatched(axis_size, y, in_batched[0])
        gnb = _broadcast_unbatched(axis_size, g_new, in_batched[1])
        gob = _broadcast_unbatched(axis_size, g_old, in_batched[2])
        hpb = hp_vec if in_batched[3] else jnp.broadcast_to(
            hp_vec[None], (axis_size,) + hp_vec.shape)
        mb = None
        if gated:
            (mask,) = rest
            mb = mask if in_batched[4] else jnp.broadcast_to(
                mask[None], (axis_size,) + mask.shape)
        out = fused_tracking_sweep_tree(yb, gnb, gob, hpb, mb, shards=shards)
        return out, tm(lambda _: True, out)

    return f


def hyper_param_vec(hyper) -> jnp.ndarray:
    """(5,) params row [lam, theta, alpha, gamma, beta] from a Hyper (or any
    object with those scalar attributes); stacked Hypers give (S, 5)."""
    vals = [jnp.asarray(v, jnp.float32) for v in
            (hyper.lam, hyper.theta, hyper.alpha, hyper.gamma, hyper.beta)]
    return jnp.stack(vals, axis=-1)


def fused_local_update(x_tree, y_tree, nu_tree, hp_vec, mask=None, *,
                       kind: str, shards: ClientShards | None = None):
    """Momentum + prox for one config's clients, sweep-major under vmap.

    ``hp_vec`` is the (5,) row [lam, theta, alpha, gamma, beta]; ``mask``
    an optional (C,) cohort gate freezing rows in-kernel; ``shards`` the
    devices' split of the client dim (the shard_map backend's).  Returns
    (x', nu').  Under ``jax.vmap`` over stacked configs this lowers to ONE
    sweep-major kernel whose grid axis 0 is the config axis.
    """
    f = _make_fused_local_update(kind, mask is not None, shards)
    with annotate("fused_kernel"):
        if mask is None:
            return f(x_tree, y_tree, nu_tree, hp_vec)
        return f(x_tree, y_tree, nu_tree, hp_vec, mask)


def fused_tracking(y_tree, g_new_tree, g_old_tree, hp_vec, mask=None, *,
                   shards: ClientShards | None = None):
    """Tracking axpy ``y' = y + beta (g_new - g_old)`` (+ in-kernel freeze
    when ``mask`` given), sweep-major under vmap.  Returns (y', g_kept)."""
    f = _make_fused_tracking(mask is not None, shards)
    with annotate("fused_kernel"):
        if mask is None:
            return f(y_tree, g_new_tree, g_old_tree, hp_vec)
        return f(y_tree, g_new_tree, g_old_tree, hp_vec, mask)
