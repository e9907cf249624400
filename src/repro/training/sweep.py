"""Batched sweep engine: vmap whole DEPOSITUM runs over configs *and graphs*.

The paper's experimental section (Figs. 3-7) is a grid study over step sizes
alpha/beta, momentum gamma, regulariser strength lam, ... and — Fig. 6 —
over the communication *topology* itself.  Historically each grid point was
a separate Python-loop run with a fresh ``jit``: first because the
hyperparameters were baked into closures (fixed by the Hyper split,
``repro.core.hyper``), then because the mixer was a closure over a concrete
W (fixed by :class:`repro.core.mixing.MixPlan`).  With both as traced
operands, an entire federated run can be ``vmap``-ed over a stacked sweep
axis: the S-point grid — hyperparameters, topologies, or both zipped —
becomes **one compiled program**: one ``lax.scan`` over rounds, vmapped over
the sweep axis, composed with the per-client ``vmap`` inside ``grad_fn``.

Shapes:
  hypers        Hyper with leaves (S,)            (or unstacked: broadcast)
  mixer         MixPlan whose leaves may carry a leading (S,) axis
                (dense: W is (S, n, n)) — the topology sweep axis — or
                a round-indexed MixSchedule whose leaves may
                carry the same leading (S,) sweep axis ahead of their
                round axis (stacked: W is (S, R, n, n); lazy: active is
                (S, R, n)) — the *schedule* sweep axis
  batches       leaves (rounds, T0, n_clients, B, ...)   shared across sweep
                or (S, rounds, T0, n_clients, B, ...)    per-config data
  final state   leaves (S, n_clients, ...)
  round outputs leaves (S, rounds, ...)

*Where* a sweep point executes is an :class:`~repro.training.backends.
ExecutionBackend`: the default ``stacked-vmap`` keeps clients on a leading
dim; passing a ``shard_map`` backend runs every point's mixing inside
``shard_map`` over a device mesh (vmap-of-shard_map), so the distributed
ppermute/all_gather path rides the same sweep axis and the same equivalence
tests as the simulation path.

Static structure (momentum kind, prox family, T0, mix *kind*,
use_fused_kernel) lives in the single ``DepositumConfig`` (plus the plan's
static fields) shared by the whole sweep; grids that vary static fields are
grouped by the caller (see ``benchmarks/common.py:run_depositum_grid``).

With ``use_fused_kernel`` (or ``fused="auto"|"require"``) the local update
does NOT run as S per-config kernels under the vmap: the fused entry points
are ``jax.custom_batching.custom_vmap`` functions whose batching rule maps
the stacked-Hyper sweep axis onto **Pallas grid axis 0** of the sweep-major
kernels (``repro.kernels.prox``) — one kernel launch per leaf covers the
whole (config, client) grid, hyperparameters ride in an (S, 5) SMEM table,
and cohort masks gate frozen rows in-kernel.  ``fused="require"`` is
checked host-side here at the sweep boundary (momentum/prox structure,
float params) before anything is traced.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.core import (
    DepositumConfig,
    DepositumState,
    Hyper,
    fused_eligibility,
    init as dep_init,
    local_then_comm_round,
    n_sweep,
)
from repro.core.compression import active_compression
from repro.core.hyper import stack_hypers
from repro.core.mixing import MixPlan, validate_plan
from repro.core.schedule import MixSchedule, validate_schedule
from repro.training.backends import (
    ExecutionBackend,
    StackedVmapBackend,
)

PyTree = Any
GradFn = Callable[[PyTree, Any], tuple[PyTree, Any]]
MetricsFn = Callable[[DepositumState, Hyper], dict]


# ---------------------------------------------------------------------------
# Data adapters: broadcast one data stream across the sweep axis
# ---------------------------------------------------------------------------

def broadcast_batches(batches: PyTree, n: int) -> PyTree:
    """Add a leading sweep dim of length ``n`` to every leaf (no copy: a
    broadcast view is materialised lazily by XLA)."""
    return jax.tree_util.tree_map(
        lambda b: jnp.broadcast_to(b[None], (n,) + b.shape), batches
    )


def sweep_batch_iter(base_iter: Iterator[PyTree], n: int) -> Iterator[PyTree]:
    """Adapter for streaming loops: yields each batch with a sweep dim."""
    for batches in base_iter:
        yield broadcast_batches(batches, n)


def stack_rounds(batch_list: Iterable[PyTree]) -> PyTree:
    """Stack per-round batch pytrees into one (rounds, ...) pytree."""
    batch_list = list(batch_list)
    return jax.tree_util.tree_map(lambda *bs: jnp.stack(bs), *batch_list)


# ---------------------------------------------------------------------------
# Sweep-operand plumbing: (MixPlan | MixSchedule) + Hyper -> vmap axes
# ---------------------------------------------------------------------------

def _mapped_len(tree, axis: Optional[int]) -> int:
    """Sweep-dim length of a pytree mapped at ``axis`` (1 when unmapped)."""
    if axis is None:
        return 1
    return int(jax.tree_util.tree_leaves(tree)[0].shape[axis])


def _take(tree, s: int, axis: Optional[int]):
    """Select sweep point ``s`` of a pytree mapped at ``axis`` (id if None)."""
    if axis is None:
        return tree
    return jax.tree_util.tree_map(lambda v: jnp.take(v, s, axis=axis), tree)


def _normalise_operands(plan, hypers, n_extra: int = 1
                        ) -> tuple[Hyper, int, Any, Any]:
    """Returns (hypers, S, hyper_axes, plan_axes).

    MixPlans — and round-indexed MixSchedules, which expose the same
    ``is_stacked``/``n_sweep``/``point`` surface over their *sweep* axis —
    are traced operands.  Unstacked operands broadcast (in_axes None);
    stacked ones map (in_axes 0) and must agree on S.  ``n_extra`` is the
    sweep length implied by other mapped operands (params_axis /
    batch_axis), so params-only or data-only sweeps with an unstacked
    Hyper/plan still size S correctly.
    """
    if not isinstance(plan, (MixPlan, MixSchedule)):
        raise TypeError("sweeps mix by a MixPlan or MixSchedule operand, "
                        f"got {type(plan).__name__}")
    S_h = n_sweep(hypers)
    hyper_stacked = jnp.ndim(hypers.alpha) > 0
    S_p = plan.n_sweep
    S = max(S_h if hyper_stacked else 1, S_p, n_extra)
    for name, stacked, length in (("Hyper", hyper_stacked, S_h),
                                  ("MixPlan/MixSchedule", plan.is_stacked,
                                   S_p),
                                  ("params/batches", n_extra > 1, n_extra)):
        if stacked and length != S:
            raise ValueError(
                f"stacked {name} axis ({length}) disagrees with the sweep "
                f"length {S} (stacked operands are zipped and must match)")
    if not hyper_stacked and not plan.is_stacked and S == 1:
        # degenerate 1-point sweep: stack the hyper so vmap has a mapped axis
        hypers = stack_hypers([hypers])
        hyper_stacked = True
    hyper_axes = 0 if hyper_stacked else None
    plan_axes = 0 if plan.is_stacked else None
    return hypers, S, hyper_axes, plan_axes


def _validate_operand(plan, n_clients: int) -> None:
    """Assumption-2 gate for either mixing operand form."""
    if isinstance(plan, MixSchedule):
        validate_schedule(plan, n_clients)
    else:
        validate_plan(plan, n_clients)


def _check_fused_boundary(config: DepositumConfig, params0=None,
                          backend=None) -> None:
    """Host-side ``fused="require"`` gate at the sweep boundary.

    The per-step eligibility check inside ``depositum.step`` would also
    raise, but only mid-trace; failing here keeps the error at the API
    surface with the structural reason (momentum kind, prox family,
    non-float params, a backend opting out) before any compilation starts.
    """
    if config.fused_mode() != "require":
        return
    ok, why = fused_eligibility(config)
    if ok and backend is not None and not getattr(
            backend, "supports_fused_sweep", True):
        ok, why = False, f"backend {backend.name!r} opts out of fused sweep"
    if ok and params0 is not None:
        for leaf in jax.tree_util.tree_leaves(params0):
            dt = jnp.asarray(leaf).dtype
            if not jnp.issubdtype(dt, jnp.floating):
                ok, why = False, f"non-float params leaf dtype {dt}"
                break
    if not ok:
        raise ValueError(f"fused='require' cannot be honoured: {why}")


def _metrics_caller(metrics_fn):
    """Normalise a metrics callback to ``f(state, hyper, plan) -> dict``.

    Two positional parameters (the classic ``metrics_fn(state, hyper)``)
    stay supported; a third receives the sweep point's mixing operand —
    cohort metrics need its sampler's eligibility mask to reduce over
    eligible rows only.  Arity is probed host-side once, outside the trace.
    """
    if metrics_fn is None:
        return lambda state, hyper, plan: {}
    import inspect

    try:
        params = [p for p in inspect.signature(metrics_fn).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
        wants_plan = len(params) >= 3
    except (TypeError, ValueError):   # builtins / partials without signature
        wants_plan = False
    if wants_plan:
        return metrics_fn
    return lambda state, hyper, plan: metrics_fn(state, hyper)


def _scanned_run(grad_fn, config, n_clients, metrics_fn, mixer_factory,
                 telemetry=None):
    """One sweep point's whole run as a scan over rounds:
    (hyper, plan, params, batches) -> (final_state, per_round_outputs).
    Shared by the vmapped and the serial paths so their computations cannot
    drift apart.  ``mixer_factory(plan) -> ScheduleMixer`` is the
    backend's execution strategy; the plan arrives as a traced operand,
    never baked in.

    With a :class:`~repro.obs.record.Telemetry` attached the returned
    runner takes two extra operands ``(tag, log_every)``: the recorder's
    ring buffer joins the scan carry, every round records the theory
    metrics on-device at the (traced) cadence, and the per-config ``tag``
    keys the host event stream — under the sweep vmap each config flushes
    its own buffer, so one compiled program emits S metric streams.  The
    training state update is untouched: metrics-on trajectories are
    bit-identical to metrics-off (pinned by tests/test_obs.py)."""
    metrics = _metrics_caller(metrics_fn)

    if telemetry is None:
        def run_one(hyper, plan, params, batches):
            mixer = mixer_factory(plan)
            # schedules carrying an active CompressionSpec need the CHOCO
            # error-feedback memory on the state; the spec arrives per sweep
            # point (its kind is static, so this branch is trace-stable)
            state0 = dep_init(params, n_clients,
                              compress=active_compression(plan))

            def body(state, batches_r):
                state, _ = local_then_comm_round(
                    state, batches_r, grad_fn, config, mixer, hyper=hyper
                )
                return state, metrics(state, hyper, plan)

            return jax.lax.scan(body, state0, batches)

        return run_one

    from repro.obs.metrics import round_values

    def run_one_tel(hyper, plan, params, batches, tag, log_every):
        mixer = mixer_factory(plan)
        state0 = dep_init(params, n_clients,
                          compress=active_compression(plan))
        n_rounds = jax.tree_util.tree_leaves(batches)[0].shape[0]

        def body(carry, batches_r):
            state, tcarry = carry
            state, aux = local_then_comm_round(
                state, batches_r, grad_fn, config, mixer, hyper=hyper
            )
            r = (state.t - 1) // config.comm_period
            vals = round_values(state, config, hyper=hyper, mixer=plan,
                                aux=aux, n=n_clients)
            tcarry = telemetry.record(tcarry, vals, r, log_every,
                                      force=r >= n_rounds - 1)
            telemetry.emit(tcarry, tag)
            return (state, tcarry), metrics(state, hyper, plan)

        (state, _), outs = jax.lax.scan(
            body, (state0, telemetry.init_carry()), batches)
        return state, outs

    return run_one_tel


def sweep_init(params0: PyTree, n_clients: int, n: int,
               compress: Any = None) -> DepositumState:
    """Initial sweep state: identical per-config, leaves (S, n_clients, ...).

    ``compress`` (a CompressionSpec or a schedule carrying one) allocates
    the CHOCO error-feedback memory on every sweep point, matching what
    :func:`sweep_run` builds internally — pass the swept schedule here
    when driving :func:`make_sweep_round` by hand."""
    state0 = dep_init(params0, n_clients, compress=compress)
    return jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), state0
    )


def make_sweep_round(
    grad_fn: GradFn,
    config: DepositumConfig,
    mixer,
    *,
    batch_axis: Optional[int] = 0,
    backend: Optional[ExecutionBackend] = None,
) -> Callable:
    """jit(vmap) of one federated round over the sweep axis.

    Returns ``round_fn(states, hypers, batches, plan=None) -> (states, aux)``
    where ``states`` leaves carry a leading sweep dim.  Use this for
    streaming loops that cannot pre-stack all rounds of data.  ``mixer``
    is a (possibly stacked) MixPlan / MixSchedule.

    The resolved plan is threaded as a **runtime operand** of the jitted
    round — per the operand contract in ``repro.training.backends``, its
    leaves are never baked into the closure — so feeding a different
    same-structure plan via the ``plan=`` argument (a new topology grid, a
    reseeded cohort) reuses the compiled program instead of retracing, and
    large stacked W leaves stay out of the program text.  ``hypers`` may be
    stacked (leaves (S,)) or unstacked — scalars broadcast over the sweep
    axis exactly as in :func:`sweep_run`.

    The default ``batch_axis=0`` matches :func:`broadcast_batches` /
    :func:`sweep_batch_iter`, whose outputs carry a leading (S,) sweep dim;
    pass ``batch_axis=None`` only when feeding raw (T0, n_clients, ...)
    batches shared across the sweep.
    """
    backend = backend or StackedVmapBackend()
    _check_fused_boundary(config, backend=backend)
    _, _, _, plan_axes = _normalise_operands(mixer, Hyper.create())

    def one(state, hyper, plan, batches):
        return local_then_comm_round(
            state, batches, grad_fn, config, backend.mixer_for(plan),
            hyper=hyper)

    vm = jax.vmap(one, in_axes=(0, 0, plan_axes, batch_axis))
    jitted = jax.jit(vm)

    def round_fn(states, hypers, batches, plan=None):
        plan_arg = mixer if plan is None else plan
        # broadcast an unstacked Hyper over the sweep axis (sweep_run's
        # documented behaviour; states always carry the sweep dim)
        if jnp.ndim(hypers.alpha) == 0:
            S = int(jax.tree_util.tree_leaves(states)[0].shape[0])
            hypers = jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(jnp.asarray(v), (S,)), hypers)
        return jitted(states, hypers, plan_arg, batches)

    return round_fn


def sweep_run(
    params0: PyTree,
    grad_fn: GradFn,
    config: DepositumConfig,
    mixer,
    hypers: Hyper,
    batches: PyTree,
    *,
    n_clients: int,
    metrics_fn: Optional[MetricsFn] = None,
    batch_axis: Optional[int] = None,
    params_axis: Optional[int] = None,
    backend: Optional[ExecutionBackend] = None,
    telemetry=None,
    log_every: int = 1,
) -> tuple[DepositumState, dict]:
    """Run ``rounds`` federated rounds for every sweep point at once.

    ``mixer``: a :class:`MixPlan` or :class:`MixSchedule`; a *stacked* plan (dense W of shape (S, n, n))
    makes the topology itself a sweep dimension, zipped with the Hyper axis.
    ``batches`` leaves: (rounds, T0, n_clients, B, ...) — shared across the
    sweep (``batch_axis=None``, the common fair-comparison case) or with an
    extra leading (S,) dim (``batch_axis=0``).  ``params_axis=0`` likewise
    sweeps the *initialisation*: params0 leaves carry a leading (S,) dim
    (used to batch per-seed runs, e.g. Table III).  ``backend`` picks where
    each point executes (default stacked-vmap simulation; a ShardMapBackend
    runs mixing inside shard_map over a device mesh).  Returns the stacked
    final state and a dict of per-round outputs with leaves (S, rounds, ...)
    (empty if ``metrics_fn`` is None).

    The whole thing is one jitted program: scan over rounds inside, vmap
    over the sweep axis outside, client vmap innermost (inside ``grad_fn``).

    ``telemetry`` (a :class:`~repro.obs.record.Telemetry`) records the
    per-round theory metrics on-device inside the scan and emits one event
    stream per config (``config=s`` matches the sweep index); ``log_every``
    is the recording cadence — a traced operand, so changing it reuses the
    compiled program (the final round always records).
    """
    backend = backend or StackedVmapBackend()
    config.validate(hypers)  # host-side range checks on the concrete grid
    _check_fused_boundary(config, params0, backend)
    n_extra = max(_mapped_len(params0, params_axis),
                  _mapped_len(batches, batch_axis))
    plan = mixer
    hypers, S, hyper_axes, plan_axes = _normalise_operands(
        plan, hypers, n_extra)
    _validate_operand(plan, n_clients)
    run_one = _scanned_run(grad_fn, config, n_clients, metrics_fn,
                           backend.mixer_for, telemetry)
    if telemetry is None:
        runner = jax.jit(jax.vmap(
            run_one,
            in_axes=(hyper_axes, plan_axes, params_axis, batch_axis)))
        final_states, outs = runner(hypers, plan, params0, batches)
    else:
        runner = jax.jit(jax.vmap(
            run_one, in_axes=(hyper_axes, plan_axes, params_axis,
                              batch_axis, 0, None)))
        final_states, outs = runner(
            hypers, plan, params0, batches,
            jnp.arange(S, dtype=jnp.int32),
            jnp.asarray(log_every, jnp.int32))
    return final_states, outs


def sweep_run_sequential(
    params0: PyTree,
    grad_fn: GradFn,
    config: DepositumConfig,
    mixer,
    hypers: Hyper,
    batches: PyTree,
    *,
    n_clients: int,
    metrics_fn: Optional[MetricsFn] = None,
    batch_axis: Optional[int] = None,
    params_axis: Optional[int] = None,
    backend: Optional[ExecutionBackend] = None,
    telemetry=None,
    log_every: int = 1,
) -> tuple[DepositumState, dict]:
    """Reference path: same computation, one sweep point at a time.

    Used by the equivalence tests and the sweep-vs-sequential wall-clock
    ratio.  Each point still runs the scanned round function, but points are
    processed serially and results re-stacked on the sweep axis.
    """
    backend = backend or StackedVmapBackend()
    config.validate(hypers)
    _check_fused_boundary(config, params0, backend)
    n_extra = max(_mapped_len(params0, params_axis),
                  _mapped_len(batches, batch_axis))
    plan = mixer
    hypers, S, hyper_axes, plan_axes = _normalise_operands(
        plan, hypers, n_extra)
    _validate_operand(plan, n_clients)  # same legality gate as sweep_run
    # the *same* scanned program as sweep_run — only the batching differs —
    # so the equivalence the tests assert is between vmap and a serial loop,
    # never between two drifting copies of the round logic
    run_one = jax.jit(_scanned_run(grad_fn, config, n_clients,
                                   metrics_fn, backend.mixer_for, telemetry))

    results = []
    for s in range(S):
        hyper_s = (jax.tree_util.tree_map(lambda v: v[s], hypers)
                   if hyper_axes == 0 else hypers)
        plan_s = plan.point(s)
        params_s = _take(params0, s, params_axis)
        batches_s = _take(batches, s, batch_axis)
        if telemetry is None:
            results.append(run_one(hyper_s, plan_s, params_s, batches_s))
        else:
            # tag / log_every are traced operands: all S points share one
            # compiled program, exactly as in the vmapped path
            results.append(run_one(
                hyper_s, plan_s, params_s, batches_s,
                jnp.asarray(s, jnp.int32),
                jnp.asarray(log_every, jnp.int32)))
    final = jax.tree_util.tree_map(lambda *vs: jnp.stack(vs),
                                   *[r[0] for r in results])
    outs = jax.tree_util.tree_map(lambda *vs: jnp.stack(vs),
                                  *[r[1] for r in results]) if results[0][1] else {}
    return final, outs


# ---------------------------------------------------------------------------
# Fedopt baselines through the same engine (Table III grids)
# ---------------------------------------------------------------------------

def sweep_run_fedalg(
    alg,
    params0: PyTree,
    grad_fn: GradFn,
    hypers: Hyper,
    batches: PyTree,
    *,
    n_clients: int,
    metrics_fn=None,
    batch_axis: Optional[int] = None,
    params_axis: Optional[int] = None,
    plan: Optional[MixPlan] = None,
) -> tuple[Any, dict]:
    """Vmap a fedopt baseline's whole run over a stacked sweep axis.

    ``alg`` is a ``repro.core.fedopt`` algorithm; its ``round`` accepts the
    same traced ``hyper`` override (and decentralized algorithms the same
    traced ``plan``) as DEPOSITUM, so Table-III baseline grids compile to
    one program per algorithm exactly like the DEPOSITUM grids.

    ``batches`` leaves: (rounds, T0, n, B, ...) — the round count is their
    leading (post-sweep-axis) dim, as in :func:`sweep_run` — optionally with
    a leading (S,) sweep dim (``batch_axis=0``).  ``params_axis=0`` sweeps
    over initialisations too (leaves (S, ...)) — used to batch the per-seed
    runs of Table III.  A scalar Hyper broadcasts over whatever defines the
    sweep axis (stacked plan, per-seed params, or per-point data), exactly
    as in :func:`sweep_run`.  Returns (final_state, outs) with a leading
    (S,) dim.
    """
    if plan is not None:
        # same Assumption-2 legality gate as sweep_run/sweep_run_sequential:
        # baseline grids must not silently run an invalid W
        _validate_operand(plan, n_clients)
    n_extra = max(_mapped_len(params0, params_axis),
                  _mapped_len(batches, batch_axis))
    plan_arg = plan if plan is not None else MixPlan.identity()
    hypers, S, hyper_axes, plan_axes = _normalise_operands(
        plan_arg, hypers, n_extra)
    metrics = _metrics_caller(metrics_fn)

    def run_one(hyper, plan_s, params, batches):
        state0 = alg.init(params, n_clients)

        def body(state, batches_r):
            kw = {"hyper": hyper}
            if plan is not None:
                kw["plan"] = plan_s
            state, _ = alg.round(state, batches_r, grad_fn, **kw)
            return state, metrics(state, hyper, plan_s)

        return jax.lax.scan(body, state0, batches)

    runner = jax.jit(jax.vmap(
        run_one, in_axes=(hyper_axes, plan_axes, params_axis, batch_axis)))
    return runner(hypers, plan_arg, params0, batches)
