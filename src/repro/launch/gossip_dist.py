"""Topology-aware distributed gossip: MixPlans under placement shard_map.

The paper-faithful mix contracts the stacked client states with the dense
mixing matrix W — under GSPMD that is an all-gather over the client axis
(O(n * |theta|) bytes per device) followed by a local contraction.  For a
sparse topology (ring: 2 neighbors) the information flow only needs
O(deg * |theta| / n) bytes: one ``lax.ppermute`` per neighbor offset inside a
``shard_map`` over the client axis.

Since the MixPlan refactor this module no longer owns the collective
schedule: the per-kind shard semantics live in
:func:`repro.core.mixing.shard_body` (shared with the generic
``ShardMapBackend``), and this module contributes only what is
placement-specific — every leaf keeps its tensor-parallel spec on the
non-client dims; only the client dim is mapped.  The result is numerically
identical to the dense mix with the corresponding circulant W (tests assert
this on a host mesh).
"""
from __future__ import annotations

from typing import Any

import jax

import jax.numpy as jnp

from repro.core.mixing import MixPlan, shard_body
from repro.core.schedule import (
    MixSchedule,
    ScheduleMixer,
    shard_compressed_qmix,
    shard_schedule_body,
    wire_supported,
)
from repro.launch.sharding import Placement, spec_for
from repro.models.common import is_axes_leaf


def plan_for_topology(topology: str, n: int) -> MixPlan:
    """The cheapest *exact* distributed plan for a named topology.

    Thin alias for ``MixPlan.from_topology(..., prefer="sparse")`` — the
    one topology -> schedule dispatcher — kept so launch-side callers don't
    need to know the preference flag.
    """
    return MixPlan.from_topology(topology, n, prefer="sparse")


def make_shardmap_mixer(placement: Placement, axes_tree: Any,
                        shapes_tree: Any, plan: MixPlan):
    """Mixer over the client mesh axes executing ``plan`` inside shard_map.

    ``axes_tree``/``shapes_tree`` describe the *state* leaves (with the
    leading 'clients' logical dim); the shard_map in/out specs are exactly
    the placement specs, so the surrounding jit sees identical shardings.
    Dispatch per plan kind (pmean / ppermute / all_gather+contract) is
    :func:`repro.core.mixing.shard_body` — the same code the sweep engine's
    ShardMapBackend runs, so the launch path and the sweep path cannot
    drift apart.
    """
    if isinstance(plan, MixSchedule):
        return make_shardmap_schedule_mixer(placement, axes_tree,
                                            shapes_tree, plan)
    mesh = placement.mesh
    caxes = placement.clients_axes
    n = placement.n_clients
    if n <= 1 or not caxes or plan.kind == "identity":
        return lambda tree: tree

    axis_name = caxes if len(caxes) > 1 else caxes[0]

    specs = jax.tree_util.tree_map(
        lambda a, s: spec_for(placement, tuple(a), s.shape),
        axes_tree, shapes_tree, is_leaf=is_axes_leaf,
    )

    def mix(tree):
        flat, treedef = jax.tree_util.tree_flatten(tree)
        flat_specs = treedef.flatten_up_to(specs)

        out_leaves = []
        for leaf, spec in zip(flat, flat_specs):
            fn = jax.shard_map(
                lambda blk: shard_body(plan, blk, axis_name, n),
                mesh=mesh, in_specs=(spec,), out_specs=spec,
            )
            out_leaves.append(fn(leaf))
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    return mix


def make_shardmap_schedule_mixer(placement: Placement, axes_tree: Any,
                                 shapes_tree: Any, schedule: MixSchedule):
    """Round-indexed placement mixer: ``mix(tree, r)`` inside shard_map.

    The per-round dispatch (lazy/cohort rounds mask each
    ppermute/all_gather contribution by the active-edge vector — sampler
    masks are redrawn identically on every shard from the replicated key —
    Chebyshev rounds unroll their k collectives, stacked/alternating
    rounds gather the round's plan operand) is
    :func:`repro.core.schedule.shard_schedule_body` — shared with the
    generic ``ShardMapBackend``, so the launch path and the sweep engine
    execute time-varying communication identically.  The round program
    supplies ``r = t // T0`` (``repro.core.depositum.step`` does this for
    any ``ScheduleMixer``, and also derives the cohort state-freeze mask
    there).
    """
    mesh = placement.mesh
    caxes = placement.clients_axes
    n = placement.n_clients
    if n <= 1 or not caxes:
        return ScheduleMixer(lambda tree, r: tree, schedule)

    axis_name = caxes if len(caxes) > 1 else caxes[0]

    specs = jax.tree_util.tree_map(
        lambda a, s: spec_for(placement, tuple(a), s.shape),
        axes_tree, shapes_tree, is_leaf=is_axes_leaf,
    )

    def mix(tree, r):
        rr = jnp.asarray(r, jnp.int32)
        flat, treedef = jax.tree_util.tree_flatten(tree)
        flat_specs = treedef.flatten_up_to(specs)

        out_leaves = []
        for leaf, spec in zip(flat, flat_specs):
            fn = jax.shard_map(
                lambda blk: shard_schedule_body(schedule, rr, blk,
                                                axis_name, n),
                mesh=mesh, in_specs=(spec,), out_specs=spec,
            )
            out_leaves.append(fn(leaf))
        return jax.tree_util.tree_unflatten(treedef, out_leaves)

    # compressed increments cross the placement collectives packed, exactly
    # as on the generic ShardMapBackend (shared shard_compressed_qmix body)
    wire = None
    if wire_supported(schedule):
        def wire(tree, r):
            rr = jnp.asarray(r, jnp.int32)
            flat, treedef = jax.tree_util.tree_flatten(tree)
            flat_specs = treedef.flatten_up_to(specs)

            out_leaves = []
            for leaf, spec in zip(flat, flat_specs):
                fn = jax.shard_map(
                    lambda blk: shard_compressed_qmix(schedule, rr, blk,
                                                      axis_name, n),
                    mesh=mesh, in_specs=(spec,), out_specs=spec,
                )
                out_leaves.append(fn(leaf))
            return jax.tree_util.tree_unflatten(treedef, out_leaves)

    return ScheduleMixer(mix, schedule, wire_fn=wire)


def make_shardmap_ring_mixer(placement: Placement, axes_tree: Any,
                             shapes_tree: Any, topology: str = "ring"):
    """Back-compat adapter: ring/complete ppermute mixer by topology name."""
    if topology not in ("ring", "complete"):
        raise ValueError(f"shardmap mixer supports ring|complete, got {topology}")
    plan = plan_for_topology(topology, placement.n_clients)
    return make_shardmap_mixer(placement, axes_tree, shapes_tree, plan)
