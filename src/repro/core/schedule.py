"""Round-indexed communication: the :class:`MixSchedule` pytree.

PR 2 made the mixing matrix a traced operand (:class:`~repro.core.mixing.
MixPlan`), but one *static* plan per run — every round communicated the
same way.  The paper's Remark 3 analyzes DEPOSITUM over **time-varying**
networks (each round only a random subgraph participates), and balancing
communication against computation round-by-round is exactly the knob the
related DFL literature turns (Liu et al.'s cost balancing, DFedAvg's
multi-gossip).  A :class:`MixSchedule` promotes the communication pattern
to a *round-indexed* operand that is scanned alongside the batches:

* ``constant``    — one plan for every round: the form every plan takes in
  the round program.  Executes exactly the plan's own ops
  (``apply_mix`` / ``shard_body``).
* ``stacked``     — plan leaves carry a leading round axis ``(R, ...)``;
  round ``r`` uses ``plan[r]`` (clamped at R-1 past the end).
* ``lazy(p, rng)``— Remark 3 partial participation: a per-round 0/1
  ``active`` mask; round ``r`` applies the lazy-subgraph matrix of the
  base plan (inactive mass folds into the diagonal).  Executed natively:
  a masked contraction for dense bases, per-offset masked rolls /
  ``ppermute``\\ s for circulant bases — never by materialising W^t on the
  host.  Masks are either pre-drawn host-side (``rounds=R`` — the
  reproducible PR 3 form, O(R n) memory) or, with ``rounds=None``, drawn
  **on device inside the scan** by a :class:`~repro.core.cohort.
  CohortSampler` (O(n) memory, any horizon).  Inactive clients skip
  *communication only* — they keep taking local steps.
* ``cohort``    — the padded / ragged client axis: a
  :class:`~repro.core.cohort.CohortSampler` draws each round's active
  cohort on device; the same mask gates **both** the mix (lazy-subgraph
  semantics over the padded dense plan) and the round program's *local
  state updates* (inactive and padding rows are frozen in place by
  ``repro.core.depositum.step``).  With a plan padded via
  :func:`~repro.core.cohort.pad_plan`, one compiled program runs any
  effective ``n <= n_max`` — ``n_clients`` becomes a sweep dimension.
* ``chebyshev(k)``— a constant schedule over a
  :meth:`MixPlan.chebyshev <repro.core.mixing.MixPlan.chebyshev>` plan:
  every round runs k accelerated gossip exchanges as one plan.
* ``alternating`` — cycles through a period-P stack of plans
  (``plan[r % P]``): the communication/computation trade studied by
  multi-local-step gossip methods.

Static structure (schedule kind, period, the plan's kind/offsets/cheby_k)
lives in aux_data; all arrays are leaves.  Like plans, schedules stack on
a leading **sweep** axis (:func:`stack_schedules`) and then vmap through
the sweep engine — ``p_active`` grids share one compiled program, and
heterogeneous grids (lazy x chebyshev) densify to a universal per-round
``stacked`` form first (:func:`as_stacked_schedule`).

Execution is split per backend exactly like plans:

* :func:`apply_schedule`      — stacked-clients simulation semantics.
* :func:`shard_schedule_body` — per-shard semantics inside ``shard_map``
  (a lazy round masks each ppermute/all_gather contribution by the
  active-edge value; a chebyshev round unrolls k collectives).

The round index ``r`` is derived by the round program from the iteration
counter (``state.t // T0``), so schedules thread through ``lax.scan``
without any API change to the scan carry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cohort import CohortSampler
from repro.core.compression import (
    CompressionSpec,
    as_mixed,
    pack_payload,
    unpack_payload,
    wire_mode,
)
from repro.core.mixing import (
    MixPlan,
    apply_mix,
    as_dense,
    shard_body,
    stack_mixplans,
    validate_plan,
)
from repro.core.topology import (
    lazy_subgraph_matrix,
    spectral_lambda,
    validate_mixing,
)

PyTree = Any

_SCHEDULE_KINDS = ("constant", "stacked", "lazy", "chebyshev", "alternating",
                   "cohort")

#: Host-side validation of round-varying schedules densifies one matrix per
#: round; with on-device samplers the horizon is unbounded, and even
#: pre-drawn R-huge schedules should not cost O(R) dense matrices at
#: validation time.  ``validate_schedule(rounds=None)`` therefore checks at
#: most this many rounds per sweep point (a documented sample — Assumption 2
#: for time-varying networks is a joint-connectivity property anyway, not a
#: per-round one).  Pass ``rounds=`` explicitly to widen or narrow the
#: sample.
VALIDATE_ROUNDS_CAP = 16


def _plan_extra_ndim(plan: MixPlan) -> int:
    """Leaf dims beyond the base rank (0 = plain, 1 = one extra axis, ...)."""
    if plan.kind == "chebyshev":
        # lam is the one leaf every chebyshev plan carries (W is None for
        # circulant bases); its base rank is 0
        return jnp.ndim(plan.lam)
    if plan.kind == "dense":
        return jnp.ndim(plan.W) - 2
    if plan.kind == "circulant":
        return jnp.ndim(plan.weights) - 1
    return 0


def _plan_lead_leaf(plan: MixPlan):
    """The leaf whose leading axes carry a plan's sweep/round stacking."""
    if plan.kind == "chebyshev":
        return plan.lam
    return plan.W if plan.kind == "dense" else plan.weights


def _point_traced(plan: MixPlan, idx) -> MixPlan:
    """Select one leading-axis point of a plan with a *traced* index."""
    return jax.tree_util.tree_map(
        lambda v: jnp.take(v, idx, axis=0, mode="clip"), plan)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MixSchedule:
    """Round-indexed communication pattern as a scanned operand.

    Build with the classmethod constructors.  ``kind`` and ``period`` are
    static; ``plan`` (a sub-pytree) and ``active`` are leaves.
    """

    kind: str                                # static
    plan: MixPlan                            # base / round-stacked plan
    active: Optional[jnp.ndarray] = None     # lazy: (R, n) or (S, R, n)
    period: int = 0                          # static (alternating only)
    sampler: Optional[CohortSampler] = None  # cohort / on-device lazy
    compress: Optional[CompressionSpec] = None  # what comm steps transmit

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.plan, self.active, self.sampler,
                self.compress), (self.kind, self.period)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, period = aux
        plan, active, sampler, compress = children
        return cls(kind=kind, plan=plan, active=active, period=period,
                   sampler=sampler, compress=compress)

    # -- constructors -------------------------------------------------------
    @classmethod
    def constant(cls, plan: MixPlan) -> "MixSchedule":
        """``plan`` on every round: exactly the plan's own ops."""
        if plan.is_stacked:
            raise ValueError(
                "constant schedules take an unstacked plan; use "
                "MixSchedule.stacked for a per-round stack, or "
                "stack_schedules for a sweep axis")
        return cls(kind="constant", plan=plan)

    @classmethod
    def stacked(cls, plans) -> "MixSchedule":
        """Per-round plans: a list of same-kind plans or an already-stacked
        plan whose leading leaf axis is the round axis."""
        plan = plans if isinstance(plans, MixPlan) else stack_mixplans(
            list(plans))
        if _plan_extra_ndim(plan) != 1:
            raise ValueError("stacked schedules need plan leaves with one "
                             "leading (rounds) axis")
        return cls(kind="stacked", plan=plan)

    @classmethod
    def alternating(cls, plans: Sequence[MixPlan]) -> "MixSchedule":
        """Cycle through ``plans``: round r communicates with plan[r % P]."""
        plans = list(plans)
        if len(plans) < 2:
            raise ValueError("alternating schedules need >= 2 plans "
                             "(use constant for one)")
        return cls(kind="alternating", plan=stack_mixplans(plans),
                   period=len(plans))

    @classmethod
    def lazy(cls, plan: MixPlan, p_active: float, rounds: int | None = None,
             *, n: int | None = None, seed: int = 0,
             rng: np.random.Generator | None = None) -> "MixSchedule":
        """Remark 3 partial participation over ``plan``'s graph.

        Each round an i.i.d. Bernoulli(``p_active``) subset of clients is
        active; only edges with BOTH endpoints active communicate, the rest
        of the mass folds into the diagonal (``lazy_subgraph_matrix``
        semantics, executed natively in-trace).  ``p_active=1.0``
        reproduces the base plan exactly.  ``n`` is required for circulant
        bases.  Inactive clients skip communication only (they keep taking
        local steps); for cohorts that freeze entirely use
        :meth:`cohort`.

        With ``rounds`` given, the ``(R, n)`` mask is pre-drawn here,
        host-side, from ``rng``/``seed`` (the reproducible PR 3 form).
        With ``rounds=None`` (and no ``rng``), no mask is materialised at
        all: a :class:`~repro.core.cohort.CohortSampler` seeded by
        ``seed`` redraws each round's mask on device inside the scan —
        O(n) memory at any horizon.
        """
        if not 0.0 <= p_active <= 1.0:
            raise ValueError(f"p_active must be in [0, 1], got {p_active}")
        if rounds is not None and rounds < 1:
            raise ValueError(f"lazy schedules need rounds >= 1 (or None "
                             f"for the on-device draw), got {rounds}")
        if plan.is_stacked:
            raise ValueError("lazy schedules take an unstacked base plan")
        if plan.kind not in ("dense", "circulant"):
            if n is None:
                raise ValueError(f"lazy over a {plan.kind!r} plan needs n "
                                 "to densify")
            plan = as_dense(plan, n)
        if plan.kind == "dense":
            n = int(plan.W.shape[-1])
        elif n is None:
            raise ValueError("lazy over a circulant plan needs n")
        if rounds is None:
            if rng is not None:
                raise ValueError("rounds=None draws masks on device; a "
                                 "host rng does not apply (use seed=)")
            sampler = CohortSampler.bernoulli(p_active, n, seed=seed)
            return cls(kind="lazy", plan=plan, sampler=sampler)
        rng = rng if rng is not None else np.random.default_rng(seed)
        mask = rng.random((rounds, n)) < p_active
        return cls(kind="lazy", plan=plan,
                   active=jnp.asarray(mask, jnp.float32))

    @classmethod
    def cohort(cls, plan: MixPlan, sampler: CohortSampler) -> "MixSchedule":
        """Padded client axis + per-round cohort participation.

        ``plan`` must be a dense ``(n_max, n_max)`` plan (pad a smaller
        graph with :func:`~repro.core.cohort.pad_plan`); ``sampler`` draws
        each round's active cohort on device.  Unlike ``lazy``, the drawn
        mask gates the *whole round*: inactive and padding rows neither
        communicate nor take local steps — ``repro.core.depositum``
        freezes them via :func:`schedule_round_mask`.  This is the DFedAvg
        ``act_prob`` / FedProx ``n_workers_per_round`` semantics, and the
        form under which ``n_clients`` sweeps (stack per-size padded plans
        and samplers with :func:`stack_schedules`).
        """
        if not isinstance(sampler, CohortSampler):
            raise TypeError("cohort schedules need a CohortSampler, got "
                            f"{type(sampler).__name__}")
        if plan.is_stacked:
            raise ValueError("cohort schedules take an unstacked plan; "
                             "stack whole schedules for a sweep axis")
        if plan.kind != "dense":
            raise ValueError(
                f"cohort schedules need a dense (padded) plan, got "
                f"{plan.kind!r}; densify/pad first (pad_plan)")
        if int(plan.W.shape[-1]) != sampler.n_max:
            raise ValueError(
                f"plan is {plan.W.shape[-1]}x{plan.W.shape[-1]} but the "
                f"sampler pads to n_max={sampler.n_max}")
        return cls(kind="cohort", plan=plan, sampler=sampler)

    @classmethod
    def chebyshev(cls, base: MixPlan, k: int,
                  n: int | None = None) -> "MixSchedule":
        """Every round = k Chebyshev-accelerated exchanges over ``base``."""
        if base.kind == "chebyshev":
            if base.cheby_k != k:
                raise ValueError(
                    f"base plan already runs k={base.cheby_k} chebyshev "
                    f"exchanges; refusing to silently ignore k={k} "
                    "(pass the raw base plan instead)")
            plan = base
        else:
            plan = MixPlan.chebyshev(base, k, n=n)
        return cls(kind="chebyshev", plan=plan)

    @classmethod
    def from_topology(cls, topology: str, n: int, **kwargs) -> "MixSchedule":
        """Constant schedule for a named topology (sugar)."""
        return cls.constant(MixPlan.from_topology(topology, n, **kwargs))

    def with_compression(self, spec: Optional[CompressionSpec]
                         ) -> "MixSchedule":
        """This schedule transmitting ``spec``-compressed payloads.

        The spec rides as a leaf sub-pytree, so rate/bits sweep with the
        schedule (``stack_schedules`` over per-rate copies).  ``spec=None``
        — and a ``kind="none"`` spec — leave the round program on the
        untouched dense path, bit-exactly.  Any other kind makes the
        round's comm step a CHOCO error-feedback exchange: the state must
        carry :class:`~repro.core.compression.CommMemory` per mixed
        variable (``repro.core.depositum.init(compress=...)``).
        """
        if spec is not None and not isinstance(spec, CompressionSpec):
            raise TypeError("with_compression takes a CompressionSpec, got "
                            f"{type(spec).__name__}")
        return dataclasses.replace(self, compress=spec)

    # -- introspection ------------------------------------------------------
    @property
    def is_stacked(self) -> bool:
        """True when the schedule carries a leading *sweep* axis (the round
        axis of ``stacked``/``alternating``/``lazy`` kinds is one level
        in)."""
        if self.kind == "cohort":
            return self.sampler.is_stacked
        if self.kind == "lazy":
            if self.active is None:      # on-device sampler draw
                return self.sampler.is_stacked
            return jnp.ndim(self.active) == 3
        extra = _plan_extra_ndim(self.plan)
        return extra == (2 if self.kind in ("stacked", "alternating")
                         else 1)

    @property
    def n_sweep(self) -> int:
        if not self.is_stacked:
            return 1
        if self.kind == "cohort" or (self.kind == "lazy" and
                                     self.active is None):
            return self.sampler.n_sweep
        if self.kind == "lazy":
            return int(self.active.shape[0])
        return int(_plan_lead_leaf(self.plan).shape[0])

    @property
    def n_rounds(self) -> Optional[int]:
        """Length of the round axis (None for round-invariant kinds —
        including sampler-driven kinds, whose on-device draws exist for
        every round).

        Rounds past the end clamp to the last entry (``alternating`` wraps
        with its period instead).
        """
        if self.kind in ("constant", "chebyshev", "alternating", "cohort"):
            return None
        if self.kind == "lazy":
            return None if self.active is None else int(
                self.active.shape[-2])
        leaf = _plan_lead_leaf(self.plan)
        return int(leaf.shape[1] if self.is_stacked else leaf.shape[0])

    def point(self, s: int) -> "MixSchedule":
        """Select one sweep point (identity on unswept schedules)."""
        if not self.is_stacked:
            return self
        return jax.tree_util.tree_map(lambda v: v[s], self)

    def _round_index(self, r):
        r = jnp.asarray(r, jnp.int32)
        if self.kind == "alternating":
            return jnp.mod(r, self.period)
        return r  # stacked/lazy clamp via take(mode="clip")

    def plan_at(self, r: int) -> MixPlan:
        """Host-side concrete effective plan for round ``r`` (unswept
        schedules only) — the reference the traced paths are tested
        against, and the validation/λ-reporting form."""
        if self.is_stacked:
            raise ValueError("select a sweep point first (schedule.point)")
        if self.kind in ("constant", "chebyshev"):
            return self.plan
        if self.kind == "alternating":
            return self.plan.point(int(r) % self.period)
        if self.kind == "stacked":
            return self.plan.point(min(int(r), self.n_rounds - 1))
        # lazy / cohort: fold this round's inactive mass into the diagonal
        if self.kind == "cohort" or self.active is None:
            a = np.asarray(self.sampler.mask_at(int(r)))
        else:
            a = np.asarray(self.active[min(int(r), self.n_rounds - 1)])
        base = self.plan if self.plan.kind == "dense" else as_dense(
            self.plan, a.shape[-1])
        Wt = lazy_subgraph_matrix(np.asarray(base.W), a > 0.5)
        return MixPlan.dense(Wt)


# ---------------------------------------------------------------------------
# Stacked-clients (simulation) execution
# ---------------------------------------------------------------------------

def _lazy_dense_matrix(W: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """In-trace lazy-subgraph matrix: W masked by the active-edge outer
    product, inactive mass folded into the diagonal (Remark 3).

    The diagonal is built as ``W_ii + (dropped off-diagonal mass)`` rather
    than ``1 - (kept mass)``: both agree up to fp for row-stochastic W, but
    this form makes an all-active mask return W *bit-exactly* (the dropped
    mass is a sum of exact zeros), which is what lets cohort/lazy runs at
    full participation pin against static-plan trajectories.
    """
    mask = (a[:, None] * a[None, :]).astype(W.dtype)
    offdiag = W - jnp.diag(jnp.diag(W))
    kept = offdiag * mask
    dropped = offdiag * (1.0 - mask)
    return kept + jnp.diag(jnp.diag(W) + jnp.sum(dropped, axis=1))


def _apply_lazy(plan: MixPlan, a: jnp.ndarray, tree: PyTree) -> PyTree:
    """One lazy round on stacked clients: dense masked contraction or
    per-offset masked rolls for circulant bases."""
    if plan.kind == "dense":
        return apply_mix(MixPlan.dense(_lazy_dense_matrix(plan.W, a)), tree)
    # circulant: out_i = x_i + sum_k w_k a_i a_{i+off_k} (x_{i+off_k} - x_i)
    ws = plan.weights

    def leaf(x):
        out = x
        for k, off in enumerate(plan.offsets):
            m = a * jnp.roll(a, -off)
            m = m.reshape(m.shape + (1,) * (x.ndim - 1)).astype(x.dtype)
            out = out + ws[k].astype(x.dtype) * m * (
                jnp.roll(x, -off, axis=0) - x)
        return out

    return jax.tree_util.tree_map(leaf, tree)


def apply_schedule(sched: MixSchedule, r, tree: PyTree) -> PyTree:
    """Round ``r``'s mix on the leading client dim of every leaf.

    ``r`` may be a Python int or a traced int32 scalar (the scan path).  A
    ``constant`` schedule executes exactly ``apply_mix(plan, tree)`` — no
    extra selects — so static-plan trajectories are reproduced bit-exactly.
    """
    if sched.kind in ("constant", "chebyshev"):
        return apply_mix(sched.plan, tree)
    if sched.kind in ("stacked", "alternating"):
        return apply_mix(_point_traced(sched.plan, sched._round_index(r)),
                         tree)
    # lazy / cohort: mask this round's edges, fold the rest to the diagonal
    a = _schedule_active_mask(sched, r)
    return _apply_lazy(sched.plan, a, tree)


def _schedule_active_mask(sched: MixSchedule, r) -> jnp.ndarray:
    """This round's (n,) 0/1 active mask for lazy/cohort schedules —
    gathered from the pre-drawn ``active`` array or redrawn on device by
    the sampler (deterministic in (key, r), so every call site agrees)."""
    if sched.active is not None:
        return jnp.take(sched.active, sched._round_index(r), axis=0,
                        mode="clip")
    return sched.sampler.mask_at(r)


def schedule_round_mask(mixer_or_sched, r) -> Optional[jnp.ndarray]:
    """The (n,) mask gating round ``r``'s *state updates*, or None.

    Only ``cohort`` schedules gate local compute (inactive/padding rows
    freeze for the whole round); ``lazy`` masks communication only, and
    every other kind updates all clients.  The round program calls this
    once per round and threads the mask through each local step.  Accepts
    a :class:`MixSchedule` or a :class:`ScheduleMixer` wrapper.
    """
    sched = getattr(mixer_or_sched, "schedule", mixer_or_sched)
    if isinstance(sched, MixSchedule) and sched.kind == "cohort":
        return sched.sampler.mask_at(r)
    return None


def as_schedule(plan_or_schedule) -> "MixSchedule":
    """Normalise a plan to a constant schedule (identity on schedules)."""
    if isinstance(plan_or_schedule, MixSchedule):
        return plan_or_schedule
    if isinstance(plan_or_schedule, MixPlan):
        return MixSchedule.constant(plan_or_schedule)
    raise TypeError(f"cannot build a MixSchedule from "
                    f"{type(plan_or_schedule).__name__}")


@dataclasses.dataclass(frozen=True)
class ScheduleMixer:
    """A round-indexed mixer: ``mix(tree, r) -> tree``.

    The one form the round program mixes with: :func:`round_mixer` builds
    it from a plan or a schedule (an execution backend's ``mixer_for``
    builds it for its placement), and ``repro.core.depositum.step``
    supplies ``r = t // T0`` from the iteration counter.

    ``wire_fn`` — when the schedule carries a packable
    :class:`~repro.core.compression.CompressionSpec` — is the backend's
    *compressed-payload* mixer ``wire_fn(q_tree, r) -> mixed q``: the
    shard_map backends pack each compressed increment into value/index
    pairs (sparse kinds) or int8 words (qsgd) before the collective, so
    the CHOCO exchange in ``depositum.step`` puts fewer bytes on the wire
    than the dense ``fn``.  None means "mix q with ``fn``" (stacked-vmap
    simulation, or an unpackable schedule kind).

    ``client_shards`` — set by the shard_map backend — is its split of the
    client dim over devices, which the fused update kernels follow
    (:class:`~repro.kernels.prox.kernel.ClientShards`); None on one device.
    """

    fn: Callable[[PyTree, Any], PyTree]
    schedule: MixSchedule
    wire_fn: Optional[Callable[[PyTree, Any], PyTree]] = None
    client_shards: Any = None

    def __call__(self, tree: PyTree, r) -> PyTree:
        return self.fn(tree, r)


def round_mixer(operand, backend=None) -> ScheduleMixer:
    """The round program's one mixing operand, from any of its forms.

    ``operand`` is a :class:`MixPlan` (the identity plan included), which
    runs as ``MixSchedule.constant(plan)``; a :class:`MixSchedule`; or a
    :class:`ScheduleMixer`, returned as it is.  ``backend`` (an execution
    backend, ``repro.training.backends``) places the mix; None mixes the
    stacked client dim with :func:`apply_schedule`.
    """
    if isinstance(operand, ScheduleMixer):
        return operand
    sched = as_schedule(operand)
    if backend is not None:
        return backend.mixer_for(sched)
    return ScheduleMixer(lambda tree, r: apply_schedule(sched, r, tree),
                         sched)


# ---------------------------------------------------------------------------
# Per-shard (shard_map) execution
# ---------------------------------------------------------------------------

def shard_schedule_body(sched: MixSchedule, r, x_blk: jnp.ndarray,
                        axis_name, n: int) -> jnp.ndarray:
    """Round ``r``'s mix for one leaf block inside ``shard_map``.

    Dispatch mirrors :func:`repro.core.mixing.shard_body` per plan kind;
    the schedule adds:

    * ``stacked``/``alternating`` — the round's plan leaves are gathered
      from the (replicated) stacked operand, then mixed as usual.
    * ``lazy``/``cohort`` + dense base — the in-trace lazy matrix masks the
      all_gather contraction's rows (sampler-driven masks are redrawn
      identically on every shard from the replicated key — no extra
      collective).  Padding rows of a cohort plan are identity rows, so
      they ride the same dispatch with zero weight.
    * ``lazy`` + circulant base — each ``ppermute`` contribution is masked
      by its active-edge value ``a_i * a_{(i+off) % n}`` (needs one client
      per device, like all circulant shard plans).
    * ``chebyshev`` — k unrolled collectives via the plan's shard dispatch.
    """
    if sched.kind in ("constant", "chebyshev"):
        return shard_body(sched.plan, x_blk, axis_name, n)
    if sched.kind in ("stacked", "alternating"):
        plan_r = _point_traced(sched.plan, sched._round_index(r))
        return shard_body(plan_r, x_blk, axis_name, n)
    # lazy / cohort
    a = _schedule_active_mask(sched, r)
    plan = sched.plan
    if plan.kind == "dense":
        Wt = _lazy_dense_matrix(plan.W, a)
        return shard_body(MixPlan.dense(Wt), x_blk, axis_name, n)
    # circulant: mask each ppermute contribution by the active-edge value
    idx = jax.lax.axis_index(axis_name)
    a_i = jnp.take(a, idx, mode="clip")
    out = x_blk
    for k, off in enumerate(plan.offsets):
        perm = [((s + off) % n, s) for s in range(n)]
        nb = jax.lax.ppermute(x_blk, axis_name, perm)
        a_nb = jnp.take(a, jnp.mod(idx + off, n), mode="clip")
        m = (a_i * a_nb).astype(x_blk.dtype)
        out = out + plan.weights[k].astype(x_blk.dtype) * m * (nb - x_blk)
    return out


def wire_supported(sched: MixSchedule) -> bool:
    """True when this schedule's compressed increments can cross the
    collectives *packed* (:func:`shard_compressed_qmix`).

    Needs a spec with a wire form (``wire_k > 0`` sparse, or qsgd) and a
    schedule whose round mix is a single exchange: the dense-base family
    (constant/stacked/alternating/lazy/cohort over dense plans — packed
    ``all_gather`` + row contraction) or a constant circulant (packed
    ``ppermute`` per offset).  Chebyshev rounds re-mix their own *output*
    k times — only the first exchange could ship packed — and identity/
    complete plans carry no per-edge payload to pack; those fall back to
    the dense collective on q (compression still shapes the values and is
    still accounted by ``repro.analysis.comm``).
    """
    if wire_mode(sched.compress) is None:
        return False
    if sched.plan.kind == "dense" and sched.kind in (
            "constant", "stacked", "alternating", "lazy", "cohort"):
        return True
    return sched.plan.kind == "circulant" and sched.kind == "constant"


def shard_compressed_qmix(sched: MixSchedule, r, q_blk: jnp.ndarray,
                          axis_name, n: int) -> jnp.ndarray:
    """Round ``r``'s mix of a compressed increment block, *packed on the
    wire*, inside ``shard_map``.

    ``q_blk`` is this shard's block of ``q = C(x - xhat)`` — sparse-valued
    (top-k / rand-k) or quantised (qsgd) rows.  Where :func:`shard_body`
    would put the dense block on the collective, this packs it first
    (:func:`~repro.core.compression.pack_payload`): value/index pairs of
    ``wire_k`` slots per row, or int8 words + a per-row norm.  The result
    equals the dense mix of q whenever the payload fits its capacity
    (``nnz <= wire_k``; qsgd levels <= 127) — rows past capacity truncate
    to their largest-magnitude entries.

    Only call under :func:`wire_supported`; the round matrix is derived
    exactly as :func:`shard_schedule_body` does, so the two paths agree on
    which edges are active.
    """
    spec = sched.compress
    tm = jax.tree_util.tree_map
    blk = q_blk.shape[0]
    flat = q_blk.reshape(blk, -1)
    d = flat.shape[-1]
    payload = pack_payload(spec, flat)
    plan = sched.plan
    if plan.kind == "circulant":
        # constant circulant: ppermute the packed payload per offset
        out = plan.self_weight.astype(q_blk.dtype) * q_blk
        for k, off in enumerate(plan.offsets):
            perm = [((s + off) % n, s) for s in range(n)]
            nb_payload = tm(
                lambda p: jax.lax.ppermute(p, axis_name, perm), payload)
            nb = unpack_payload(spec, nb_payload, d, q_blk.dtype)
            out = out + plan.weights[k].astype(q_blk.dtype) * nb.reshape(
                q_blk.shape)
        return out
    # dense family: all_gather the packed payload, unpack every client's
    # q row, contract with this shard's rows of the round matrix
    gathered = tm(
        lambda p: jax.lax.all_gather(p, axis_name, axis=0, tiled=True),
        payload)
    q_full = unpack_payload(spec, gathered, d, q_blk.dtype).reshape(
        (n,) + q_blk.shape[1:])
    if sched.kind in ("stacked", "alternating"):
        W = _point_traced(sched.plan, sched._round_index(r)).W
    elif sched.kind in ("lazy", "cohort"):
        W = _lazy_dense_matrix(plan.W, _schedule_active_mask(sched, r))
    else:
        W = plan.W
    idx = jax.lax.axis_index(axis_name)
    rows = jax.lax.dynamic_slice_in_dim(W, idx * blk, blk, axis=0)
    return jnp.einsum("in,n...->i...", rows.astype(q_blk.dtype), q_full,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Sweep plumbing: schedules as a sweep dimension
# ---------------------------------------------------------------------------

def stack_schedules(schedules: Sequence[MixSchedule]) -> MixSchedule:
    """Stack same-structure schedules on a new leading sweep axis.

    All schedules must agree on kind, period, and the plan's static
    structure (so e.g. a ``p_active`` grid of lazy schedules over one graph
    stacks directly).  Grids that mix schedule kinds — or chebyshev orders,
    which are static — must densify to a common per-round ``stacked`` form
    first: ``stack_schedules([as_stacked_schedule(s, rounds, n) ...])``.
    """
    schedules = list(schedules)
    if not schedules:
        raise ValueError("need at least one MixSchedule to stack")
    specs = [s.compress for s in schedules]
    if any(sp is not None for sp in specs):
        # a compression grid: normalise the specs to one static structure
        # (mixed kinds dispatch through a traced kind_id) so e.g. a
        # topk-rates x qsgd-bits x none-baseline grid stacks — and runs —
        # as one program
        specs = [CompressionSpec.none() if sp is None else sp
                 for sp in specs]
        if len({(sp.kind, sp.wire_k, sp.wire_bits) for sp in specs}) > 1 \
                or specs[0].kind == "mixed":
            specs = [as_mixed(sp) for sp in specs]
        schedules = [dataclasses.replace(s, compress=sp)
                     for s, sp in zip(schedules, specs)]
    auxs = {(s.kind, s.period, s.plan.kind, s.plan.offsets, s.plan.cheby_k,
             s.plan.base_kind,
             None if s.sampler is None else (s.sampler.kind,
                                             s.sampler.n_max),
             None if s.compress is None else (s.compress.kind,
                                              s.compress.wire_k,
                                              s.compress.wire_bits))
            for s in schedules}
    if len(auxs) > 1:
        raise ValueError(
            f"cannot stack heterogeneous schedules ({len(auxs)} distinct "
            "static structures); densify to a common per-round form first "
            "(as_stacked_schedule)")
    if any(s.is_stacked for s in schedules):
        raise ValueError("schedules are already sweep-stacked")
    if schedules[0].plan.kind in ("complete", "identity"):
        raise ValueError(
            f"{schedules[0].plan.kind!r} plans carry no arrays to stack; "
            "densify first (as_stacked_schedule / as_dense)")
    return jax.tree_util.tree_map(lambda *vs: jnp.stack(vs), *schedules)


def as_stacked_schedule(sched: MixSchedule, rounds: int,
                        n: int | None = None) -> MixSchedule:
    """Densified universal sweep form: per-round dense W of shape (R, n, n).

    Host-side (concrete schedules only).  Any schedule kind — including
    chebyshev orders, whose k is static — reduces to this form, so
    heterogeneous schedule grids stack into one compiled program.
    """
    if sched.is_stacked:
        raise ValueError("as_stacked_schedule expects an unswept schedule")
    if sched.kind == "cohort":
        raise ValueError(
            "cohort schedules do not densify: the drawn mask also gates "
            "local state updates, which a per-round W stack cannot "
            "express — sweep cohort schedules directly (stack_schedules)")
    Ws = np.stack([np.asarray(as_dense(sched.plan_at(r), n).W)
                   for r in range(rounds)])
    return MixSchedule(kind="stacked", plan=MixPlan.dense(Ws))


def validate_schedule(sched: MixSchedule, n: int | None = None,
                      atol: float = 1e-6, rounds: int | None = None) -> None:
    """Assumption-2 checks per sweep point, per distinct round (host-side).

    Round-varying kinds (stacked/lazy/alternating) are allowed
    non-contracting matrices in isolation — time-varying networks only need
    *joint* connectivity (Remark 3: contraction in expectation) — while a
    round-invariant plan (constant/chebyshev) that never contracts would
    never mix at all and is rejected.  Chebyshev plans — and stacked /
    alternating rounds, which may be densified chebyshev matrices — are
    allowed negative entries (symmetry + rows summing to one is the
    invariant that keeps the tracking identity alive); lazy masks of a
    nonnegative base stay nonnegative by construction and are checked
    strictly.  Cohort schedules are checked like lazy ones (padding rows
    are identity rows and isolate cleanly).

    With ``rounds=None``, round-varying kinds are sampled at no more than
    :data:`VALIDATE_ROUNDS_CAP` rounds per sweep point — densifying one
    host matrix per round does not scale to R-huge or unbounded
    (sampler-driven) horizons.
    """
    for s in range(sched.n_sweep) if sched.is_stacked else (None,):
        ss = sched if s is None else sched.point(s)
        if ss.kind in ("lazy", "cohort"):
            # per-round lazy matrices re-derive their diagonal and are
            # row-stochastic by construction — a defective BASE plan (rows
            # not summing to 1, negative edges) would slip through the
            # round loop, so check it directly (identity padding rows of a
            # cohort plan validate cleanly; connectivity is per-round)
            validate_plan(ss.plan, n, atol=atol, connected=False)
        if ss.kind in ("constant", "chebyshev"):
            R = 1
        elif ss.kind == "alternating":
            R = ss.period
        else:
            horizon = ss.n_rounds  # None for sampler-driven kinds
            if rounds is not None:
                R = rounds if horizon is None else min(rounds, horizon)
            elif horizon is None:
                R = VALIDATE_ROUNDS_CAP
            else:
                R = min(horizon, VALIDATE_ROUNDS_CAP)
        for r in range(R):
            plan_r = ss.plan_at(r)
            if ss.kind in ("stacked", "alternating"):
                validate_mixing(np.asarray(as_dense(plan_r, n).W),
                                atol=atol, allow_negative=True,
                                connected=False)
            else:
                validate_plan(plan_r, n, atol=atol,
                              connected=(ss.kind in ("constant",
                                                     "chebyshev")))


def schedule_spectral_lambda(sched: MixSchedule, n: int | None = None,
                             rounds: int = 1) -> np.ndarray:
    """Per-round lambda = ||W^t - J|| over the first ``rounds`` rounds.

    Returns (rounds,) for unswept schedules, (S, rounds) for swept ones.
    Host-side, concrete schedules only.
    """
    if sched.is_stacked:
        return np.stack([schedule_spectral_lambda(sched.point(s), n, rounds)
                         for s in range(sched.n_sweep)])
    return np.asarray([
        spectral_lambda(np.asarray(as_dense(sched.plan_at(r), n).W))
        for r in range(rounds)])
