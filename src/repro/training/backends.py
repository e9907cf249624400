"""Execution backends: one round program, three ways to run it.

A backend answers exactly one question — *how does a*
:class:`~repro.core.schedule.MixSchedule` *execute on this placement* —
so the DEPOSITUM round program
(``local_then_comm_round``), the sweep engine, the launchers, and the
fedopt baselines can all share it:

* :class:`StackedVmapBackend` (``"stacked-vmap"``) — single-process
  simulation: every client variable is stacked on a leading dim and mixing
  is a plain jnp contraction (:func:`repro.core.mixing.apply_mix`).
* :class:`ShardMapBackend` (``"shard_map"``) — the client dim is sharded
  over a named mesh axis; mixing runs inside ``shard_map`` per leaf
  (``pmean`` for complete, one ``ppermute`` per circulant offset,
  ``all_gather`` + local row contraction for dense W — W stays a traced
  operand, so a stacked-W sweep can vmap *over* the shard_map).
* :class:`SweepBackend` (``"sweep"``) — vmaps whole federated runs over a
  stacked Hyper/MixPlan axis, delegating per-point mixing to an ``inner``
  backend (default stacked-vmap; pass a ShardMapBackend to ride the sweep
  axis over the distributed path).

``get_backend("stacked-vmap" | "shard_map" | "sweep", ...)`` builds one by
name.  All backends expose ``mixer_for(operand) -> ScheduleMixer``: the
operand is a schedule or a plan, and a plan runs as the constant schedule
``MixSchedule.constant(plan)``.  Operands with traced leaves must be
threaded as operands (the sweep engine does this), never baked into a jit
closure, or the one-program-per-grid guarantee is lost.

Fused local compute: the *mixing* strategy above is orthogonal to the
local-update kernel.  With ``config.use_fused_kernel`` the round program's
update is a sweep-major Pallas kernel (``repro.kernels.prox``) whose grid
axis 0 is the stacked-config axis; on the stacked-vmap backend the sweep
engine's vmap maps straight onto that grid axis (one launch per leaf for
the whole grid), while on the shard_map backend each device updates its
own client rows: the mixer carries the backend's ``client_shards`` and the
kernel runs under ``shard_map`` with that split (a Mosaic kernel cannot be
partitioned by XLA).  ``supports_fused_sweep`` advertises this; it is True
for every in-tree backend and exists so out-of-tree placements can opt
out.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.schedule import (
    MixSchedule,
    ScheduleMixer,
    as_schedule,
    round_mixer,
    shard_compressed_qmix,
    shard_schedule_body,
    wire_supported,
)
from repro.kernels.prox.kernel import ClientShards


def _plan_kind(plan_or_schedule) -> str:
    """Effective collective kind: a schedule's base plan, a chebyshev
    plan's base — the thing that decides ppermute vs all_gather.  Cohort
    schedules resolve to their padded dense base, so masked/padded rows
    ride the ordinary all_gather + row-contraction dispatch (padding rows
    are identity rows with zero weight in every active contraction)."""
    plan = (plan_or_schedule.plan if isinstance(plan_or_schedule, MixSchedule)
            else plan_or_schedule)
    return plan.base_kind if plan.kind == "chebyshev" else plan.kind


@runtime_checkable
class ExecutionBackend(Protocol):
    """The contract every backend satisfies."""

    name: str
    #: Whether ``depositum.step``'s sweep-major fused kernel may run on this
    #: placement (all in-tree backends: yes — the local update is outside
    #: the mixing collective on every one of them).  ``training.sweep``
    #: consults this before honouring ``fused="require"``.
    supports_fused_sweep: bool

    def mixer_for(self, operand) -> ScheduleMixer:  # pragma: no cover
        ...

    def place(self, state: Any) -> Any:  # pragma: no cover
        """Put a state (leaves with a leading client dim) where the
        backend's round program expects it."""
        ...


@dataclasses.dataclass(frozen=True)
class StackedVmapBackend:
    """Simulation semantics: leading client dim, jnp-only mixing.

    ``mixer_for`` takes a plan or a schedule and returns a
    ``ScheduleMixer`` — ``mix(tree, r)``, which the round program drives
    from ``t // T0`` — over :func:`~repro.core.schedule.apply_schedule`.
    """

    name: str = dataclasses.field(default="stacked-vmap", init=False)
    supports_fused_sweep: bool = dataclasses.field(default=True, init=False)

    def mixer_for(self, operand) -> ScheduleMixer:
        return round_mixer(as_schedule(operand))

    def place(self, state):
        return state


@dataclasses.dataclass(frozen=True)
class ShardMapBackend:
    """Client dim sharded over ``axis_name`` of ``mesh``.

    ``n_clients`` is the *global* client count (leading-dim length of the
    state leaves).  Circulant plans additionally require one client per
    device on the axis (the ppermute schedule is per-shard); dense and
    complete plans accept any equal block size.
    """

    mesh: Any
    axis_name: str = "clients"
    n_clients: int = 0
    name: str = dataclasses.field(default="shard_map", init=False)
    #: The fused local update runs on each device's client rows, in its
    #: own shard_map (``client_shards``) outside the mixing.
    supports_fused_sweep: bool = dataclasses.field(default=True, init=False)

    @property
    def client_shards(self) -> ClientShards:
        return ClientShards(self.mesh, self.axis_name)

    def _axis_size(self) -> int:
        if isinstance(self.axis_name, tuple):
            size = 1
            for a in self.axis_name:
                size *= self.mesh.shape[a]
            return size
        return self.mesh.shape[self.axis_name]

    def _check_plan(self, plan) -> tuple[int, int]:
        size = self._axis_size()
        n = self.n_clients or size
        if n % size != 0:
            raise ValueError(
                f"n_clients={n} not divisible by mesh axis "
                f"{self.axis_name!r} of size {size}")
        if _plan_kind(plan) == "circulant" and n != size:
            raise ValueError(
                "circulant (ppermute) plans need one client per device; "
                f"got n_clients={n} on a {size}-way axis — use a dense plan")
        return size, n

    def place(self, state):
        """Shard every leaf's leading client dim over the axis (scalars,
        such as the iteration counter, are replicated)."""
        def put(leaf):
            spec = P(self.axis_name) if jnp.ndim(leaf) else P()
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(put, state)

    def mixer_for(self, operand) -> ScheduleMixer:
        """Round-indexed mixer of a plan or schedule: per-round
        ``shard_body`` variants (masked ppermute/all_gather for lazy
        rounds, unrolled collectives for chebyshev) inside one
        ``shard_map`` per leaf.

        When the schedule carries a packable
        :class:`~repro.core.compression.CompressionSpec`, the returned
        mixer also exposes ``wire_fn``: the compressed increment q crosses
        the collective *packed* (value/index pairs or int8 words via
        ``shard_compressed_qmix``) instead of dense-shaped, so the CHOCO
        exchange in ``depositum.step`` actually shrinks bytes on the wire.
        """
        sched = as_schedule(operand)
        size, _n = self._check_plan(sched)
        spec = P(self.axis_name)

        def placed(body):
            """``body(sched, r, blk, axis, size)`` per leaf in shard_map."""
            def run(tree, r):
                rr = jnp.asarray(r, jnp.int32)
                return jax.tree_util.tree_map(lambda x: jax.shard_map(
                    lambda blk: body(sched, rr, blk, self.axis_name, size),
                    mesh=self.mesh, in_specs=(spec,), out_specs=spec)(x),
                    tree)

            return run

        wire = placed(shard_compressed_qmix) if wire_supported(sched) else None
        return ScheduleMixer(placed(shard_schedule_body), sched, wire_fn=wire,
                             client_shards=self.client_shards)


@dataclasses.dataclass(frozen=True)
class SweepBackend:
    """Grid semantics: vmap whole runs over stacked Hyper/MixPlan axes.

    ``mixer_for`` delegates to the inner backend (one sweep *point*'s
    mixing); ``run`` is the full engine — it simply forwards to
    :func:`repro.training.sweep.sweep_run` with ``backend=self.inner`` so
    there is exactly one implementation of the grid loop.
    """

    inner: ExecutionBackend = dataclasses.field(
        default_factory=StackedVmapBackend)
    name: str = dataclasses.field(default="sweep", init=False)

    @property
    def supports_fused_sweep(self) -> bool:
        return getattr(self.inner, "supports_fused_sweep", True)

    def mixer_for(self, operand) -> ScheduleMixer:
        return self.inner.mixer_for(operand)

    def place(self, state):
        return self.inner.place(state)

    def run(self, params0, grad_fn, config, mixer, hypers, batches, *,
            n_clients: int, metrics_fn=None, batch_axis=None,
            telemetry=None, log_every: int = 1):
        from repro.training.sweep import sweep_run

        return sweep_run(params0, grad_fn, config, mixer, hypers, batches,
                         n_clients=n_clients, metrics_fn=metrics_fn,
                         batch_axis=batch_axis, backend=self.inner,
                         telemetry=telemetry, log_every=log_every)


#: Per-device bytes/round below which a comm round is latency-bound — the
#: collective costs more in dispatch than it moves, and the single-process
#: stacked-vmap simulation wins.  A deliberately conservative 4 KiB (a few
#: packets): only *heavily* compressed payloads duck under it.
LATENCY_BYTES_FLOOR = 4096


def suggest_backend_name(kind: str, n_clients: int, n_devices: int, *,
                         wire_bytes: float | None = None) -> str:
    """Pure decision rule for :func:`suggest_backend` (testable host-side).

    * circulant (incl. chebyshev-over-circulant) plans want the ppermute
      path, which needs exactly one client per device;
    * dense/complete plans want the all_gather/pmean path whenever the
      device count divides the client count;
    * anything else (single device, indivisible counts, identity) runs the
      stacked-vmap simulation.

    ``wire_bytes`` — per-round bytes one device puts on the wire, computed
    from the **compressed** payload
    (:func:`repro.analysis.comm.device_wire_bytes`), not the dense leaf
    size — refines the choice: a schedule whose compressed payload drops
    below :data:`LATENCY_BYTES_FLOOR` makes every collective latency-bound,
    so the simulation backend is preferred even where the dense payload
    would have picked shard_map.  ``None`` (no spec / unknown sizes) keeps
    the structural rule exactly.
    """
    if n_devices > 1 and n_clients > 1:
        latency_bound = wire_bytes is not None and \
            wire_bytes < LATENCY_BYTES_FLOOR
        if kind == "circulant":
            if n_devices == n_clients and not latency_bound:
                return "shard_map"
            return "stacked-vmap"
        if kind in ("dense", "complete") and n_clients % n_devices == 0 \
                and not latency_bound:
            return "shard_map"
    return "stacked-vmap"


def suggest_backend(plan_or_schedule, n_clients: int, *,
                    devices=None, axis_name: str = "clients",
                    param_dim: int | None = None) -> ExecutionBackend:
    """Pick the execution backend from the plan's sparsity and the host.

    The last PR 2 follow-up: callers (``FederatedTrainer`` by default) no
    longer hand-pick a mesh — a circulant plan gets the ppermute shard_map
    path when one device per client exists, a dense/complete plan gets the
    all_gather/pmean path when the device count divides ``n_clients``, and
    everything else falls back to the stacked-vmap simulation (always
    correct, single-device friendly).

    ``param_dim`` (flattened per-client parameter count) enables the
    payload-aware refinement: for schedules carrying a
    :class:`~repro.core.compression.CompressionSpec`, the per-device
    bytes/round of the *compressed* payload decide whether the collective
    is worth dispatching at all (see :func:`suggest_backend_name`).
    """
    devices = list(devices) if devices is not None else jax.devices()
    wire_bytes = None
    if param_dim is not None and isinstance(plan_or_schedule, MixSchedule) \
            and plan_or_schedule.compress is not None \
            and not plan_or_schedule.is_stacked:
        from repro.analysis.comm import device_wire_bytes

        wire_bytes = device_wire_bytes(plan_or_schedule, param_dim,
                                       n_clients, len(devices))
    name = suggest_backend_name(_plan_kind(plan_or_schedule), n_clients,
                                len(devices), wire_bytes=wire_bytes)
    if name == "shard_map":
        # Auto axes: the round program stays in XLA's propagation mode (the
        # model zoo's reshapes have no explicit-sharding rules)
        mesh = jax.make_mesh((len(devices),), (axis_name,),
                             axis_types=(AxisType.Auto,), devices=devices)
        return ShardMapBackend(mesh=mesh, axis_name=axis_name,
                               n_clients=n_clients)
    return StackedVmapBackend()


def get_backend(name: str, *, mesh=None, axis_name: str = "clients",
                n_clients: int = 0,
                inner: Optional[ExecutionBackend] = None) -> ExecutionBackend:
    """Build a backend by its protocol name."""
    if name == "stacked-vmap":
        return StackedVmapBackend()
    if name == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        return ShardMapBackend(mesh=mesh, axis_name=axis_name,
                               n_clients=n_clients)
    if name == "sweep":
        return SweepBackend(inner=inner or StackedVmapBackend())
    raise KeyError(
        f"unknown backend {name!r}; have stacked-vmap | shard_map | sweep")
