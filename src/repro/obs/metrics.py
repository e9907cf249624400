"""In-loop theory metrics: what a DEPOSITUM round should *record*.

The paper's claims are trajectories — Theorem 1 bounds the running means of
the proximal gradient mapping, the consensus errors, and the gradient
estimation error by O(1/T) — yet :func:`repro.core.stationarity_metrics`
computes them only at *eval* points, with exact full-data gradients the
round program never sees.  This module defines the **in-loop** counterparts:
every quantity below is a cheap function of the round program's own state
(no extra gradient evaluations, no host sync), so it can be recorded every
round from inside the ``lax.scan`` on any backend:

* ``prox_grad_sq``   — ``(1/n) Σ_i ‖(x_i − prox_{αh}(x_i − α ν_i))/α‖²``:
  the gradient-mapping norm of Definition 2 evaluated along the *momentum
  direction* ν (the algorithm's own gradient estimate) instead of the exact
  global gradient.  Exactly recomputable post hoc from a saved state.
* ``consensus_x`` / ``consensus_y`` — ``(1/n) ‖(I − J) v‖²`` for the
  iterates and the tracking variable; **bit-identical** to
  ``stationarity_metrics``'s ``consensus_x`` / ``consensus_y`` (same
  reduction, same dtype path).
* ``momentum_var``   — ``(1/n) ‖(I − J) ν‖²``, the cross-client variance of
  the momentum direction (= ``consensus_nu`` of ``stationarity_metrics``).
* ``track_err``      — ``(1/n) Σ_i ‖y_i − β ḡ‖²`` with ``ḡ`` the client
  mean of the last stochastic gradients: the in-loop (stochastic) proxy for
  the tracking estimation error ``‖y_i − (β/n) Σ_j ∇f_j‖²`` — the exact
  form needs fresh full-data gradients and stays in
  ``stationarity_metrics``.
* ``cohort_size``    — clients active this round (padding/inactive rows
  excluded); ``n`` for full participation.
* ``wire_bytes``     — algorithmic bytes-on-wire of this round's gossip,
  the *traced* twin of :mod:`repro.analysis.comm` (same counting rules,
  jnp instead of numpy, so lazy/cohort rounds count the mask actually
  drawn inside the scan).  Collective-free rounds would count the comm
  step's bytes; the recorder records per-*round* values, i.e. one comm
  step per round.
* ``loss``           — the round's training loss from the grad aux: mean of
  ``aux["ce"]`` when present, else mean of ``aux["loss"]`` (the scalar
  loss every :mod:`repro.models` zoo model and ``value_and_grad`` trainer
  reports), else NaN.  NaN — not a missing key — is the "no loss" value,
  so streams stay rectangular.
* ``staleness``      — mean age (in learner rounds) of the client updates
  *applied* this round.  Bulk-synchronous rounds apply only fresh work,
  so every synchronous path records an identical 0.0; the async runtime
  (:mod:`repro.training.async_runtime`) passes its per-round mean through
  ``round_values(staleness=...)``.  0.0 — not NaN — is the sync value so
  sync/async streams compare directly.

All values are float32 scalars; :mod:`repro.obs.record` packs them into the
scan-carried buffer in :data:`DEFAULT_METRICS` order.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.analysis.comm import (
    INDEX_BYTES,
    QSGD_NORM_BYTES,
    QSGD_WORD_BYTES,
    VALUE_BYTES,
)
from repro.core.compression import KIND_IDS, CompressionSpec
from repro.core.depositum import DepositumConfig, DepositumState, _sq_norm, \
    _client_mean, consensus_error
from repro.core.hyper import Hyper
from repro.core.mixing import MixPlan
from repro.core.prox import prox_apply
from repro.core.schedule import (
    MixSchedule,
    ScheduleMixer,
    _point_traced,
    _schedule_active_mask,
)

PyTree = Any

#: Every in-loop metric the recorder knows, in buffer-column order.
DEFAULT_METRICS = ("prox_grad_sq", "consensus_x", "consensus_y",
                   "momentum_var", "track_err", "cohort_size",
                   "wire_bytes", "loss", "staleness")


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """Static recorder structure: which metrics, how many device rows.

    ``names`` picks (and orders) the recorded columns; ``buffer`` is the
    number of logged rows held on device between ``io_callback`` flushes.
    Both are *static* — changing them retraces (they shape the carry);
    the logging cadence is a **runtime operand** instead
    (:meth:`repro.obs.record.Telemetry.record`), so cadence toggles never
    recompile.
    """

    names: tuple = DEFAULT_METRICS
    buffer: int = 8

    def __post_init__(self):
        unknown = [n for n in self.names if n not in DEFAULT_METRICS]
        if unknown:
            raise ValueError(f"unknown metrics {unknown}; "
                             f"have {DEFAULT_METRICS}")
        if self.buffer < 1:
            raise ValueError(f"buffer must be >= 1, got {self.buffer}")

    @property
    def n_metrics(self) -> int:
        return len(self.names)


# ---------------------------------------------------------------------------
# Traced bytes-on-wire: the jnp twin of repro.analysis.comm
# ---------------------------------------------------------------------------

def traced_payload_row_bytes(spec: Optional[CompressionSpec],
                             d: int) -> jnp.ndarray:
    """Bytes one client row ships per collective, as a traced f32 scalar.

    Mirrors :func:`repro.analysis.comm.payload_row_bytes` rule for rule
    (dense f32 rows; value+index pairs at ``wire_k`` or the traced-rate
    ``ceil(rate·d)``; int8 qsgd words + one norm; mixed kinds dispatch on
    the traced ``kind_id``), but in jnp so sweep-traced specs account
    in-loop.  The host/traced pair is pinned equal by tests.
    """
    d = int(d)
    dense = jnp.float32(d * VALUE_BYTES)
    if spec is None or spec.kind == "none":
        return dense

    def sparse_bytes():
        if spec.wire_k > 0:
            return jnp.float32(min(spec.wire_k, d)
                               * (VALUE_BYTES + INDEX_BYTES))
        rate = jnp.asarray(spec.rate, jnp.float32)
        k = jnp.clip(jnp.round(rate * d), 1, d)
        return (k * (VALUE_BYTES + INDEX_BYTES)).astype(jnp.float32)

    quant = jnp.float32(d * QSGD_WORD_BYTES + QSGD_NORM_BYTES)
    if spec.kind in ("topk", "randk"):
        return sparse_bytes()
    if spec.kind == "qsgd":
        return quant
    # mixed: elementwise dispatch on the traced kind_id leaf (which may be
    # sweep-stacked (S,) while dense/quant are scalars — hence where, not
    # a stacked table)
    kid = jnp.minimum(jnp.asarray(spec.kind_id, jnp.int32),
                      len(KIND_IDS) - 1)
    return jnp.where(kid == KIND_IDS["none"], dense,
                     jnp.where(kid == KIND_IDS["qsgd"], quant,
                               sparse_bytes())).astype(jnp.float32)


def _offdiag_mask(W: jnp.ndarray, atol: float = 1e-12) -> jnp.ndarray:
    """0/1 mask of W's nonzero off-diagonal entries (traced-safe)."""
    off = W - jnp.diag(jnp.diag(W))
    return (jnp.abs(off) > atol).astype(jnp.float32)


def traced_round_edges(sched: MixSchedule, r,
                       active_mask: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Transmitting directed edges of round ``r``'s collective, traced.

    Follows :func:`repro.analysis.comm.round_edges` exactly, but counts the
    mask *actually drawn* for lazy/cohort rounds (``active_mask``, else the
    schedule's own draw at ``r``) instead of the sampler expectation.
    """
    plan = sched.plan
    if sched.kind in ("stacked", "alternating"):
        W_r = _point_traced(plan, sched._round_index(r)).W
        return jnp.sum(_offdiag_mask(W_r))
    base = plan.base_plan() if plan.kind == "chebyshev" else plan
    if base.kind == "identity":
        return jnp.float32(0.0)
    if base.kind == "circulant":
        n = None
        if sched.kind in ("lazy", "cohort"):
            a = (active_mask if active_mask is not None
                 else _schedule_active_mask(sched, r))
            edges = sum(jnp.sum(a * jnp.roll(a, -off))
                        for off in base.offsets)
            return jnp.asarray(edges, jnp.float32)
        # edge count needs n; circulant plans carry no W — offsets are
        # per-client, so a constant circulant round transmits n per offset,
        # but n is not in the plan.  Callers with circulant constants pass
        # n via round_wire_bytes(..., n=).
        raise ValueError("constant circulant edge counts need n; use "
                         "traced_round_bytes(..., n=)")
    if base.kind == "complete":
        raise ValueError("complete-plan edge counts need n; use "
                         "traced_round_bytes(..., n=)")
    off = _offdiag_mask(base.W)
    if sched.kind in ("lazy", "cohort"):
        a = (active_mask if active_mask is not None
             else _schedule_active_mask(sched, r))
        off = off * (a[:, None] * a[None, :])
    return jnp.sum(off)


def traced_round_bytes(sched, r, d: int, *,
                       active_mask: Optional[jnp.ndarray] = None,
                       n: Optional[int] = None,
                       n_vars: int = 2) -> jnp.ndarray:
    """Bytes on the wire for one comm round, as a traced f32 scalar.

    The in-loop twin of :func:`repro.analysis.comm.round_wire_bytes`:
    transmitting edges × per-row payload × collectives (chebyshev k) ×
    mixed variables (x and y ⇒ 2).  Accepts a :class:`MixSchedule`, a
    backend ``ScheduleMixer``, or a plain :class:`MixPlan` (constant
    semantics).  ``n`` is only needed for structureless plans (complete /
    constant circulant) whose edge count is not derivable from leaves.
    """
    if isinstance(sched, ScheduleMixer):
        sched = sched.schedule
    if isinstance(sched, MixPlan):
        sched = MixSchedule.constant(sched)
    if not isinstance(sched, MixSchedule):
        # no plan or schedule: no structure to account
        return jnp.float32(float("nan"))
    plan = sched.plan
    base = plan.base_plan() if plan.kind == "chebyshev" else plan
    collectives = max(1, plan.cheby_k) if plan.kind == "chebyshev" else 1
    if base.kind in ("complete", "circulant") and sched.kind not in (
            "lazy", "cohort"):
        if n is None:
            return jnp.float32(float("nan"))
        edges = jnp.float32(n * (n - 1) if base.kind == "complete"
                            else n * len(base.offsets))
    else:
        edges = traced_round_edges(sched, r, active_mask)
    per_row = traced_payload_row_bytes(sched.compress, d)
    return edges * per_row * jnp.float32(collectives * n_vars)


# ---------------------------------------------------------------------------
# The per-round metric values
# ---------------------------------------------------------------------------

def _loss_from_aux(aux) -> jnp.ndarray:
    """Scalar training loss from a grad aux, NaN when unavailable.

    ``aux["ce"]`` (the zoo models' cross entropy) wins; ``aux["loss"]``
    (the trainer's value_and_grad scalar) is the documented fallback; any
    other shape records NaN so streams stay rectangular.
    """
    if isinstance(aux, dict):
        for key in ("ce", "loss"):
            v = aux.get(key)
            if v is not None and jnp.issubdtype(
                    jnp.asarray(v).dtype, jnp.floating):
                return jnp.mean(jnp.asarray(v)).astype(jnp.float32)
        return jnp.float32(float("nan"))
    if aux is not None and hasattr(aux, "dtype") and jnp.issubdtype(
            jnp.asarray(aux).dtype, jnp.floating):
        return jnp.mean(jnp.asarray(aux)).astype(jnp.float32)
    return jnp.float32(float("nan"))


def prox_gap_sq(state: DepositumState, config: DepositumConfig,
                hyper: Optional[Hyper] = None,
                weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``(1/n) Σ_i ‖(x_i − prox_{αh}(x_i − α ν_i))/α‖²`` — the in-loop
    gradient-mapping norm along the momentum direction.

    Shared by the recorder and the post-hoc recompute tests, so the two
    are the *same computation*, not two drifting copies.
    """
    hp = config.hyper() if hyper is None else hyper
    tm = jax.tree_util.tree_map
    if weights is None:
        n = jnp.float32(jax.tree_util.tree_leaves(state.x)[0].shape[0])
    else:
        n = jnp.sum(weights.astype(jnp.float32))
    shifted = tm(lambda p, v: p - hp.alpha * v, state.x, state.nu)
    proxed = prox_apply(config.prox_name, shifted, hp.alpha,
                        lam=hp.lam, theta=hp.theta)
    G = tm(lambda p, q: (p - q) / hp.alpha, state.x, proxed)
    return _sq_norm(G, weights) / n


def tracking_error(state: DepositumState, config: DepositumConfig,
                   hyper: Optional[Hyper] = None,
                   weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``(1/n) Σ_i ‖y_i − β ḡ‖²`` with ḡ the client mean of ``state.g`` —
    the stochastic in-loop proxy for ``‖y_i − (β/n) Σ_j ∇f_j‖²``."""
    hp = config.hyper() if hyper is None else hyper
    tm = jax.tree_util.tree_map
    if weights is None:
        n = jnp.float32(jax.tree_util.tree_leaves(state.x)[0].shape[0])
    else:
        n = jnp.sum(weights.astype(jnp.float32))
    gbar = _client_mean(state.g, weights)
    diff = tm(lambda y, g: y - jnp.asarray(hp.beta, y.dtype) * g[None],
              state.y, gbar)
    return _sq_norm(diff, weights) / n


def round_values(
    state: DepositumState,
    config: DepositumConfig,
    *,
    hyper: Optional[Hyper] = None,
    mixer: Any = None,
    aux: Any = None,
    active_mask: Optional[jnp.ndarray] = None,
    weights: Optional[jnp.ndarray] = None,
    d: Optional[int] = None,
    n: Optional[int] = None,
    staleness: Any = None,
) -> dict:
    """All :data:`DEFAULT_METRICS` for the round that just finished.

    Call on the **post-round** state (``state.t`` already advanced);
    the round index is ``(t − 1) // T0``.  ``mixer`` — the round program's
    schedule/plan operand — enables ``wire_bytes`` and, for cohort
    schedules, derives the eligibility ``weights`` and this round's
    ``active_mask`` when not given.  ``d`` is the flattened per-client
    parameter count (defaults to the state's leaf sizes).  Reads only;
    never mutates the state — metrics-on trajectories are bit-identical
    to metrics-off ones.  ``staleness`` is the mean applied-update age
    this round (async runtime); ``None`` records 0.0 — the value every
    bulk-synchronous round has by construction.
    """
    sched = getattr(mixer, "schedule", mixer)
    r = (state.t - 1) // config.comm_period
    if isinstance(sched, MixSchedule) and sched.kind == "cohort":
        if weights is None:
            weights = sched.sampler.eligible()
        if active_mask is None:
            active_mask = sched.sampler.mask_at(r)
    if d is None:
        d = sum(int(jnp.size(l)) // int(l.shape[0])
                for l in jax.tree_util.tree_leaves(state.x))
    if weights is None:
        n_cl = jnp.float32(jax.tree_util.tree_leaves(state.x)[0].shape[0])
    else:
        n_cl = jnp.sum(weights.astype(jnp.float32))
    cohort = (jnp.sum(active_mask.astype(jnp.float32))
              if active_mask is not None else n_cl)
    if isinstance(sched, (MixSchedule, MixPlan)):
        wire = traced_round_bytes(sched, r, d, active_mask=active_mask, n=n)
    else:
        wire = jnp.float32(float("nan"))
    return {
        "prox_grad_sq": prox_gap_sq(state, config, hyper, weights),
        "consensus_x": consensus_error(state.x, weights) / n_cl,
        "consensus_y": consensus_error(state.y, weights) / n_cl,
        "momentum_var": consensus_error(state.nu, weights) / n_cl,
        "track_err": tracking_error(state, config, hyper, weights),
        "cohort_size": jnp.asarray(cohort, jnp.float32),
        "wire_bytes": jnp.asarray(wire, jnp.float32),
        "loss": _loss_from_aux(aux),
        "staleness": jnp.asarray(0.0 if staleness is None else staleness,
                                 jnp.float32),
    }
