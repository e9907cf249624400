"""Topology-aware distributed gossip: MixPlans under placement shard_map.

The paper-faithful mix contracts the stacked client states with the dense
mixing matrix W — under GSPMD that is an all-gather over the client axis
(O(n * |theta|) bytes per device) followed by a local contraction.  For a
sparse topology (ring: 2 neighbors) the information flow only needs
O(deg * |theta| / n) bytes: one ``lax.ppermute`` per neighbor offset inside a
``shard_map`` over the client axis.

The per-kind shard semantics live in :func:`repro.core.mixing.shard_body`
and, per round, :func:`repro.core.schedule.shard_schedule_body` (shared
with the generic ``ShardMapBackend``); this module contributes only what
is placement-specific — every leaf keeps its tensor-parallel spec on the
non-client dims; only the client dim is mapped.  The result is numerically
identical to the dense mix with the corresponding W (tests assert this on
a host mesh).  A named topology's cheapest exact plan is
``MixPlan.from_topology(topology, n, prefer="sparse")``.
"""
from __future__ import annotations

from typing import Any

import jax

import jax.numpy as jnp

from repro.core.schedule import (
    ScheduleMixer,
    as_schedule,
    shard_compressed_qmix,
    shard_schedule_body,
    wire_supported,
)
from repro.launch.sharding import Placement, spec_for
from repro.models.common import is_axes_leaf


def make_shardmap_mixer(placement: Placement, axes_tree: Any,
                        shapes_tree: Any, operand) -> ScheduleMixer:
    """Round-indexed placement mixer of a plan or schedule: ``mix(tree, r)``
    inside shard_map (a plan runs as ``MixSchedule.constant(plan)``).

    ``axes_tree``/``shapes_tree`` describe the *state* leaves (with the
    leading 'clients' logical dim); the shard_map in/out specs are exactly
    the placement specs, so the surrounding jit sees identical shardings.
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
    schedule = as_schedule(operand)
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

    def placed(body):
        """``body(schedule, r, blk, axis, n)`` per leaf, in shard_map."""
        def run(tree, r):
            rr = jnp.asarray(r, jnp.int32)
            flat, treedef = jax.tree_util.tree_flatten(tree)
            out = [jax.shard_map(
                lambda blk: body(schedule, rr, blk, axis_name, n),
                mesh=mesh, in_specs=(spec,), out_specs=spec)(leaf)
                for leaf, spec in zip(flat, treedef.flatten_up_to(specs))]
            return jax.tree_util.tree_unflatten(treedef, out)

        return run

    # compressed increments cross the placement collectives packed, exactly
    # as on the generic ShardMapBackend (shared shard_compressed_qmix body)
    wire = placed(shard_compressed_qmix) if wire_supported(schedule) else None
    return ScheduleMixer(placed(shard_schedule_body), schedule, wire_fn=wire)
