"""Mixing as a *traced operand*: the :class:`MixPlan` pytree.

Historically every mixer was a Python closure over a concrete W (or fixed
ppermute offsets), so the topology was baked into the compiled program the
same way step sizes used to be before the Hyper split — sweeping over
networks (paper Fig. 6, the lambda = ||W - J|| dependence of the bounds)
meant one fresh jit per graph.  A :class:`MixPlan` moves the mixing data
into a pytree operand:

* ``dense``     — W itself is a runtime array ``(n, n)``.  Stacking plans
  gives a ``(S, n, n)`` leaf that ``vmap``s exactly like a stacked
  :class:`~repro.core.hyper.Hyper` axis, so ``sweep_run`` gains *topology*
  as a sweepable dimension: one compiled program for a whole
  ring/star/torus/complete grid.
* ``circulant`` — static neighbor ``offsets`` plus traced ``weights`` and
  ``self_weight``: the sparse-gossip form that lowers to one
  ``lax.ppermute`` per offset inside ``shard_map`` (ring: 2, torus: 4).
* ``complete``  — W = J: client mean (``lax.pmean`` under ``shard_map``).
* ``identity``  — W = I: the local (no-communication) step.
* ``chebyshev`` — P_k(W) over a dense/circulant base: ``cheby_k`` unrolled
  applications of the base mix via the T_k recurrence (the accelerated
  mixing protocol), with the base spectral quantity ``lam`` a traced leaf.

Round-indexed (time-varying) communication builds on these plans in
``repro.core.schedule`` (:class:`MixSchedule`).

Static structure (kind, offsets) lives in pytree aux_data, so plans of the
same kind share one traced program; the arrays are leaves.  Execution is
split per backend (``repro.training.backends``):

* :func:`apply_mix` — stacked-clients simulation semantics (leading dim of
  every leaf is the client axis).
* :func:`shard_body` — per-shard semantics for a named mesh axis, to be
  called inside ``shard_map`` (ppermute / pmean / all_gather+contract).

The round program does not take a plan directly: it wraps it as a
constant :class:`~repro.core.schedule.MixSchedule`, whose execution is
exactly these two functions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import (
    chebyshev_matrix,
    mixing_matrix,
    spectral_lambda,
    validate_mixing,
)
from repro.kernels.mix import dense_mix

PyTree = Any

_KINDS = ("dense", "circulant", "complete", "identity", "chebyshev")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MixPlan:
    """Mixing matrix as data: pytree leaves carry W (or circulant weights).

    Build with the classmethod constructors; do not mutate.  ``kind``,
    ``offsets``, ``cheby_k`` and ``base_kind`` are static (aux_data): two
    plans trace to the same program iff they agree on them.

    The ``chebyshev`` kind wraps a *base* plan (dense or circulant leaves,
    recorded in ``base_kind``) plus its spectral quantity ``lam`` as a
    traced leaf; applying it unrolls ``cheby_k`` applications of the base
    mix through the T_k recurrence — k gossip exchanges as one plan.
    """

    kind: str                               # static
    offsets: tuple[int, ...] = ()           # static (circulant only)
    W: Optional[jnp.ndarray] = None         # dense: (n, n) or (S, n, n)
    weights: Optional[jnp.ndarray] = None   # circulant: (k,) or (S, k)
    self_weight: Optional[jnp.ndarray] = None  # circulant: () or (S,)
    lam: Optional[jnp.ndarray] = None       # chebyshev: () or (S,) base lam
    cheby_k: int = 0                        # static (chebyshev only)
    base_kind: str = ""                     # static (chebyshev only)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return ((self.W, self.weights, self.self_weight, self.lam),
                (self.kind, self.offsets, self.cheby_k, self.base_kind))

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, offsets, cheby_k, base_kind = aux
        W, weights, self_weight, lam = children
        return cls(kind=kind, offsets=offsets, W=W, weights=weights,
                   self_weight=self_weight, lam=lam, cheby_k=cheby_k,
                   base_kind=base_kind)

    # -- constructors -------------------------------------------------------
    @classmethod
    def dense(cls, W) -> "MixPlan":
        return cls(kind="dense", W=jnp.asarray(W, jnp.float32))

    @classmethod
    def circulant(cls, offsets_weights: Sequence[tuple[int, float]],
                  self_weight: float) -> "MixPlan":
        offs = tuple(int(o) for o, _ in offsets_weights)
        ws = jnp.asarray([w for _, w in offsets_weights], jnp.float32)
        return cls(kind="circulant", offsets=offs, weights=ws,
                   self_weight=jnp.asarray(self_weight, jnp.float32))

    @classmethod
    def complete(cls) -> "MixPlan":
        return cls(kind="complete")

    @classmethod
    def chebyshev(cls, base: "MixPlan", k: int,
                  n: int | None = None) -> "MixPlan":
        """Chebyshev-accelerated plan: k base-gossip exchanges per round.

        ``base`` must be an unstacked dense or circulant plan with concrete
        (host-side) symmetric W — the spectral quantity lam = ||W - J|| is
        computed here and rides along as a traced leaf, so stacked
        chebyshev plans sweep like any other.  ``n`` is required for
        circulant bases (a circulant plan does not know its ring size).
        Rejects ``k < 1`` and non-symmetric bases outright.
        """
        if k < 1:
            raise ValueError(f"MixPlan.chebyshev needs k >= 1, got k={k}")
        if base.kind == "chebyshev":
            raise ValueError("cannot nest chebyshev plans; raise k instead")
        if base.is_stacked:
            raise ValueError("build chebyshev plans per point, then "
                             "stack_mixplans them")
        if base.kind not in ("dense", "circulant"):
            raise ValueError(
                f"chebyshev base must be dense or circulant, got "
                f"{base.kind!r} (densify with as_dense first)")
        Wd = np.asarray(base.W if base.kind == "dense"
                        else as_dense(base, n).W)
        if not np.allclose(Wd, Wd.T, atol=1e-6):
            raise ValueError("chebyshev base W must be symmetric "
                             "(Assumption 2)")
        lam = spectral_lambda(Wd)
        return cls(kind="chebyshev", offsets=base.offsets, W=base.W,
                   weights=base.weights, self_weight=base.self_weight,
                   lam=jnp.asarray(lam, jnp.float32), cheby_k=int(k),
                   base_kind=base.kind)

    def base_plan(self) -> "MixPlan":
        """The underlying single-exchange plan of a chebyshev plan."""
        if self.kind != "chebyshev":
            return self
        return MixPlan(kind=self.base_kind, offsets=self.offsets, W=self.W,
                       weights=self.weights, self_weight=self.self_weight)

    @classmethod
    def identity(cls) -> "MixPlan":
        return cls(kind="identity")

    @classmethod
    def from_topology(cls, topology: str, n: int, *, prefer: str = "dense",
                      **kwargs) -> "MixPlan":
        """Plan for a named topology (``repro.core.topology.TOPOLOGIES``).

        ``prefer="dense"`` (default) always returns a dense plan — the
        sweepable form.  ``prefer="sparse"`` returns the cheapest
        communication schedule that is *exact* for the topology: complete
        (or any graph on n <= 1 clients) -> pmean, ring -> circulant
        (n == 2 degenerates to the single shared edge), else dense.  (The
        torus circulant is an approximation of the grid graph — see
        :func:`repro.core.gossip.torus_circulant_spec` — so it is never
        chosen implicitly.)  This is the single source of truth for the
        topology -> schedule decision: the launch path
        (``launch.gossip_dist``) and the sweep backends both call it.
        """
        if prefer == "sparse":
            if topology == "complete" or n <= 1:
                return cls.complete()
            if topology == "ring":
                if n == 2:
                    return cls.circulant([(+1, 0.5)], 0.5)
                return cls.circulant([(+1, 1 / 3), (-1, 1 / 3)], 1 / 3)
        W = mixing_matrix(topology, n, **kwargs)
        return cls.dense(W)

    # -- introspection ------------------------------------------------------
    @property
    def is_stacked(self) -> bool:
        """True when the plan carries a leading sweep axis."""
        if self.kind == "dense":
            return self.W is not None and jnp.ndim(self.W) == 3
        if self.kind == "circulant":
            return self.weights is not None and jnp.ndim(self.weights) == 2
        if self.kind == "chebyshev":
            return self.lam is not None and jnp.ndim(self.lam) == 1
        return False

    @property
    def n_sweep(self) -> int:
        if not self.is_stacked:
            return 1
        if self.kind == "chebyshev":
            return int(self.lam.shape[0])
        leaf = self.W if self.kind == "dense" else self.weights
        return int(leaf.shape[0])

    def point(self, s: int) -> "MixPlan":
        """Select one point of a stacked plan (identity on unstacked)."""
        if not self.is_stacked:
            return self
        return jax.tree_util.tree_map(lambda v: v[s], self)


def stack_mixplans(plans: Sequence[MixPlan]) -> MixPlan:
    """Stack same-structure plans on a new leading sweep axis.

    All plans must share kind (and offsets).  To sweep over *different*
    topologies, densify first: ``stack_mixplans([as_dense(p) for p in ...])``.
    """
    if not plans:
        raise ValueError("need at least one MixPlan to stack")
    kinds = {p.kind for p in plans}
    auxs = {(p.kind, p.offsets, p.cheby_k, p.base_kind) for p in plans}
    if len(auxs) > 1:
        raise ValueError(
            f"cannot stack heterogeneous plans (kinds={sorted(kinds)}, "
            f"{len(auxs)} distinct static structures); convert to dense "
            "first (as_dense) so W is the sweep leaf")
    if plans[0].kind in ("complete", "identity"):
        raise ValueError(
            f"{plans[0].kind!r} plans carry no arrays to stack; "
            "use as_dense(plan, n) to sweep over them")
    return jax.tree_util.tree_map(lambda *vs: jnp.stack(vs), *plans)


def as_dense(plan: MixPlan, n: int | None = None) -> MixPlan:
    """Dense equivalent of any (unstacked) plan — the universal sweep form."""
    if plan.is_stacked:
        raise ValueError("as_dense expects an unstacked plan")
    if plan.kind == "dense":
        return plan
    if n is None:
        raise ValueError(f"as_dense({plan.kind!r}) needs n")
    if plan.kind == "identity":
        return MixPlan.dense(jnp.eye(n))
    if plan.kind == "complete":
        return MixPlan.dense(jnp.full((n, n), 1.0 / n))
    if plan.kind == "chebyshev":
        base = plan.base_plan()
        Wd = base.W if base.kind == "dense" else as_dense(base, n).W
        # host-side: concrete plans only (chebyshev_matrix is numpy)
        return MixPlan.dense(chebyshev_matrix(np.asarray(Wd), plan.cheby_k))
    # circulant
    W = jnp.zeros((n, n))
    W = W + jnp.diag(jnp.full((n,), plan.self_weight))
    rows = jnp.arange(n)
    for off, w in zip(plan.offsets, list(plan.weights)):
        W = W.at[rows, (rows + off) % n].add(w)
    return MixPlan.dense(W)


def plan_spectral_lambda(plan: MixPlan, n: int | None = None) -> np.ndarray:
    """Per-point lambda = ||W - J|| of a (possibly stacked) concrete plan.

    Host-side: call outside jit, on concrete plans only.  Returns a scalar
    for unstacked plans, an (S,) array for stacked ones.
    """
    if plan.is_stacked:
        return np.asarray([plan_spectral_lambda(plan.point(s), n)
                           for s in range(plan.n_sweep)])
    if plan.kind == "complete":
        return np.asarray(0.0)
    if plan.kind == "identity":
        return np.asarray(1.0)
    W = np.asarray(as_dense(plan, n).W)
    return np.asarray(spectral_lambda(W))


def validate_plan(plan: MixPlan, n: int | None = None,
                  atol: float = 1e-6, *, connected: bool = True) -> None:
    """Assumption-2 checks on a concrete plan (host-side, per sweep point).

    Chebyshev plans are validated on their densified P_k(W) with the
    nonnegativity check relaxed (negative entries are the documented, benign
    departure from Assumption 2).  ``connected=False`` skips the lambda < 1
    check — used for per-round lazy matrices (Remark 3), which need not
    contract individually.
    """
    if plan.kind in ("complete", "identity"):
        return
    for s in range(plan.n_sweep) if plan.is_stacked else (None,):
        p = plan if s is None else plan.point(s)
        validate_mixing(np.asarray(as_dense(p, n).W), atol=atol,
                        allow_negative=(p.kind == "chebyshev"),
                        connected=connected)


# ---------------------------------------------------------------------------
# Stacked-clients (simulation) execution
# ---------------------------------------------------------------------------

def _chebyshev_apply(mixfn, lam, k: int, tree: PyTree) -> PyTree:
    """P_k(W) x via the T_k recurrence: k applications of ``mixfn``.

    ``mixfn`` is one application of the base mix on this backend (apply_mix
    for stacked clients, shard_body under shard_map), so the same recurrence
    drives both.  ``lam`` is the base plan's traced spectral scalar; the
    lam -> 0 limit (complete graph) degenerates to a single exchange,
    matching :func:`repro.core.topology.chebyshev_matrix`.
    """
    tm = jax.tree_util.tree_map
    Wx = mixfn(tree)
    if k == 1:
        return Wx  # P_1(W) = W exactly
    lam32 = jnp.asarray(lam, jnp.float32)
    inv = 1.0 / jnp.maximum(lam32, 1e-12)

    def cast(s, leaf):
        return jnp.asarray(s, leaf.dtype)

    Tm2, Tm1 = tree, tm(lambda w: cast(inv, w) * w, Wx)
    tm2, tm1 = 1.0, inv
    for _ in range(k - 1):
        WT = mixfn(Tm1)
        Tm2, Tm1 = Tm1, tm(
            lambda w, p: 2.0 * cast(inv, w) * w - p, WT, Tm2)
        tm2, tm1 = tm1, 2.0 * inv * tm1 - tm2
    accelerate = lam32 > 1e-9
    return tm(lambda tk, wx: jnp.where(accelerate, tk / cast(tm1, tk), wx),
              Tm1, Wx)


def apply_mix(plan: MixPlan, tree: PyTree) -> PyTree:
    """x_i <- sum_j W_ij x_j on the leading client dim of every leaf.

    Works under jit/vmap/scan with the plan's arrays as traced operands.
    The dense path mixes each leaf with W in the leaf's dtype, on a TPU by
    the Pallas mix kernel in the leaf's own layout
    (:func:`repro.kernels.mix.dense_mix`).
    The circulant path uses ``jnp.roll`` per offset — out_i picks up
    x[(i + off) % n], matching both ``circulant_from_mixer_spec`` and the
    ppermute perm ``[((s + off) % n, s)]``.
    """
    tm = jax.tree_util.tree_map
    if plan.kind == "identity":
        return tree
    if plan.kind == "chebyshev":
        base = plan.base_plan()
        return _chebyshev_apply(lambda t: apply_mix(base, t), plan.lam,
                                plan.cheby_k, tree)
    if plan.kind == "complete":
        return tm(lambda x: jnp.broadcast_to(jnp.mean(x, axis=0,
                                                      keepdims=True),
                                             x.shape), tree)
    if plan.kind == "dense":
        return tm(lambda x: dense_mix(plan.W, x), tree)
    # circulant: out_i = self_w * x_i + sum_k w_k * x[(i + off_k) % n]
    sw, ws = plan.self_weight, plan.weights

    def leaf(x):
        out = sw.astype(x.dtype) * x
        for k, off in enumerate(plan.offsets):
            out = out + ws[k].astype(x.dtype) * jnp.roll(x, -off, axis=0)
        return out

    return tm(leaf, tree)


# ---------------------------------------------------------------------------
# Per-shard (shard_map) execution
# ---------------------------------------------------------------------------

def shard_body(plan: MixPlan, x_blk: jnp.ndarray, axis_name,
               n: int) -> jnp.ndarray:
    """Mix one leaf *block* inside ``shard_map`` over ``axis_name``.

    ``x_blk`` carries the local clients slice on its leading dim.  Kinds:

    * complete  — ``lax.pmean`` (one all-reduce).
    * circulant — one ``lax.ppermute`` per offset (bytes ~ deg/n of dense).
    * dense     — ``all_gather`` + local contraction with this shard's W
      rows; W rides in via closure (replicated) or pre-sharded rows.
    * chebyshev — ``cheby_k`` unrolled applications of the base kind's
      collective (k ppermute rounds for a circulant base).
    * identity  — no-op.
    """
    if plan.kind == "identity":
        return x_blk
    if plan.kind == "chebyshev":
        base = plan.base_plan()
        return _chebyshev_apply(
            lambda blk: shard_body(base, blk, axis_name, n),
            plan.lam, plan.cheby_k, x_blk)
    if plan.kind == "complete":
        # mean within the local client block, then across shards: the global
        # client mean for any equal block size (blk == 1: plain pmean)
        local = jnp.mean(x_blk, axis=0, keepdims=True)
        return jnp.broadcast_to(jax.lax.pmean(local, axis_name), x_blk.shape)
    if plan.kind == "circulant":
        out = plan.self_weight.astype(x_blk.dtype) * x_blk
        for k, off in enumerate(plan.offsets):
            perm = [((s + off) % n, s) for s in range(n)]
            out = out + plan.weights[k].astype(x_blk.dtype) * jax.lax.ppermute(
                x_blk, axis_name, perm)
        return out
    # dense: gather all client blocks, contract with our rows of W
    gathered = jax.lax.all_gather(x_blk, axis_name, axis=0, tiled=True)
    idx = jax.lax.axis_index(axis_name)
    blk = x_blk.shape[0]
    rows = jax.lax.dynamic_slice_in_dim(plan.W, idx * blk, blk, axis=0)
    return jnp.einsum("in,n...->i...", rows.astype(x_blk.dtype), gathered,
                      precision=jax.lax.Precision.HIGHEST)
