"""DEPOSITUM (Algorithm 1): Decentralized fEderated PrOximal Stochastic
gradIent Tracking with momentUM.

Per-iteration, for every client i (all clients stacked on a leading dim):

  1. momentum      nu^{t+1} from y^t                     (OPTION I/II)
  2. prox descent  x^{t+1} = W^t prox_{alpha h}(x^t - alpha nu^{t+1})
  3. fresh grads   g^{t+1} = minibatch grad at x^{t+1}
  4. tracking      y^{t+1} = W^t (y^t + beta g^{t+1} - beta g^t)

with W^t = W only when t is a communication step (t in {T0, 2T0, ...}),
otherwise W^t = I (local update).  Initialisation: x^0 = x0 for all clients,
mu^0 = nu^0 = y^0 = g^0 = 0 (paper's initialisation, which keeps the tracking
identity J y^t = beta J g^t for all t).

The implementation is pytree-generic: ``x`` may be a parameter pytree whose
leaves have a leading ``n_clients`` dim, so the same code drives a linear
model and a 314B MoE.

Hyperparameters are split in two (see ``repro.core.hyper``):

* :class:`DepositumConfig` — *static structure*: momentum kind, prox family,
  T0, fused-kernel flag.  Changing any of these changes the traced program.
* :class:`Hyper` — *continuous* values (alpha, beta, gamma, lam, theta) as a
  pytree of jnp scalars, passed as a traced operand so a whole sweep of them
  shares one compiled program.  Every entry point takes ``hyper=None`` and
  falls back to the config's float fields, preserving the classic API.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import (
    CommMemory,
    CompressionSpec,
    active_compression,
    choco_mix,
    comm_memory,
    comm_round_keys,
)
from repro.core.gossip import identity_mixer
from repro.core.hyper import Hyper
from repro.core.schedule import round_mixer, schedule_round_mask
from repro.core.momentum import MomentumKind, momentum_update
from repro.obs.trace import annotate
from repro.core.prox import (
    ProxOperator,
    family_params,
    get_family,
    get_prox,
    host_max,
    host_min,
    is_concrete,
    prox_apply,
)

PyTree = Any


def _scoped(name, fn):
    """fn under a phase's named scope (trace-time metadata only)."""
    def wrapped(*args):
        with annotate(name):
            return fn(*args)
    return wrapped


_FUSED_MODES = ("auto", "require", "off")


@dataclasses.dataclass(frozen=True)
class DepositumConfig:
    alpha: float = 0.05          # prox-descent step size
    beta: float = 1.0            # tracking step size (Remark 1)
    gamma: float = 0.8           # momentum coefficient in [0, 1)
    momentum: MomentumKind = "polyak"
    comm_period: int = 1         # T0: communicate when (t+1) % T0 == 0
    prox_name: str = "l1"
    prox_kwargs: dict = dataclasses.field(default_factory=lambda: {"lam": 1e-4})
    # when True, use a fused Pallas kernel for momentum+prox (TPU path)
    use_fused_kernel: bool = False
    # explicit fused-kernel policy: "auto" uses the kernel whenever this
    # step is eligible (and silently falls back otherwise), "require"
    # raises on the first ineligible step, "off" never fuses.  None keeps
    # the legacy behaviour of ``use_fused_kernel`` (True -> "auto").
    fused: str | None = None

    def fused_mode(self) -> str:
        """Resolved fused policy ("auto" | "require" | "off")."""
        if self.fused is not None:
            if self.fused not in _FUSED_MODES:
                raise ValueError(
                    f"fused must be one of {_FUSED_MODES}, got {self.fused!r}")
            return self.fused
        return "auto" if self.use_fused_kernel else "off"

    def hyper(self) -> Hyper:
        """Continuous hyperparameters of this config as a Hyper pytree."""
        lam, theta = family_params(self.prox_name, self.prox_kwargs)
        return Hyper.create(alpha=self.alpha, beta=self.beta,
                            gamma=self.gamma, lam=lam, theta=theta)

    def validate(self, hyper: Hyper | None = None) -> None:
        """Host-side range checks; traced sweep values are skipped.

        With ``hyper=None`` this checks the config's Python floats only —
        pure host arithmetic, cheap enough to run every ``step``.  With a
        concrete (possibly stacked) Hyper it reduces over the sweep axis on
        the host; call it once at the sweep boundary (``sweep_run`` does).
        """
        if self.comm_period < 1:
            raise ValueError("comm_period (T0) must be >= 1")
        self.fused_mode()  # raises on an unknown fused policy
        fam = get_family(self.prox_name)
        if hyper is None:
            alpha, gamma = self.alpha, self.gamma
            lam, theta = family_params(self.prox_name, self.prox_kwargs)
        else:
            alpha, gamma = hyper.alpha, hyper.gamma
            lam, theta = hyper.lam, hyper.theta

        if is_concrete(theta):
            fam.check_params(lam, theta)
            if is_concrete(alpha):
                # elementwise worst point over (possibly stacked) sweep axes;
                # numpy only: jnp would be staged into tracers under jit
                rho = np.asarray(fam.rho_fn(np.asarray(theta, np.float32)))
                worst = float(np.max(np.asarray(alpha, np.float32) * rho))
                if float(np.max(rho)) > 0.0 and worst >= 1.0:
                    raise ValueError(
                        f"prox of weakly convex {self.prox_name} needs "
                        f"alpha*rho < 1, got max alpha*rho = {worst}"
                    )
        if is_concrete(gamma):
            if not (0.0 <= host_min(gamma) and host_max(gamma) < 1.0):
                raise ValueError(f"gamma must be in [0,1), got {gamma}")

    def make_prox(self) -> ProxOperator:
        prox = get_prox(self.prox_name, **self.prox_kwargs)
        prox.check_step(self.alpha)
        self.validate()
        return prox


def fused_eligibility(config: "DepositumConfig", state=None,
                      hyper: Hyper | None = None) -> tuple[bool, str]:
    """Can the fused (sweep-major) Pallas kernels serve this step?

    Returns ``(ok, reason)`` with ``reason`` naming the first blocker: the
    kernels cover Polyak momentum over the l1 | mcp | scad prox chain, on
    floating-point state leaves, with *scalar* per-step hyperparameters —
    a stacked Hyper must ride the sweep engine's vmap (where the custom
    batching rule maps it onto grid axis 0), never reach ``step`` raw.
    """
    if config.momentum != "polyak":
        return False, (f"momentum={config.momentum!r} (kernel covers "
                       "'polyak' only)")
    if config.prox_name not in ("l1", "mcp", "scad"):
        return False, (f"prox_name={config.prox_name!r} (kernel covers "
                       "l1 | mcp | scad)")
    if state is not None:
        for leaf in jax.tree_util.tree_leaves((state.x, state.y, state.nu,
                                               state.g)):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return False, (f"non-float state leaf dtype {leaf.dtype} "
                               "(kernel is float-only)")
    if hyper is not None and jnp.ndim(hyper.alpha) > 0:
        return False, ("stacked Hyper passed directly to step (vmap the "
                       "run over the sweep axis instead)")
    return True, "eligible"


class DepositumState(NamedTuple):
    """All client variables; every leaf has leading dim = n_clients.

    ``comm`` is the compressed-communication memory: ``()`` (no leaves)
    for dense runs, else ``{"x": CommMemory, "y": CommMemory}`` — one
    CHOCO error-feedback pair (public copy ``xhat`` + running mix ``s``)
    per mixed variable, built by ``init(compress=...)`` and updated on
    every comm step.  An empty ``comm`` keeps the scan carry identical to
    pre-compression states.
    """

    x: PyTree       # model parameters (per client)
    y: PyTree       # gradient-tracking variable
    nu: PyTree      # momentum-aggregated direction
    mu: PyTree      # auxiliary momentum (Nesterov only; zeros otherwise)
    g: PyTree       # last stochastic gradient estimate
    t: jnp.ndarray  # iteration counter (int32 scalar)
    comm: Any = ()  # compressed-gossip error-feedback memory (or ())


def _zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _broadcast_clients(params: PyTree, n_clients: int) -> PyTree:
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n_clients,) + p.shape), params
    )


def init(params: PyTree, n_clients: int, stacked: bool = False,
         n_max: int | None = None,
         compress: Any = None) -> DepositumState:
    """Initial state: identical x across clients, all auxiliaries zero.

    ``n_max`` pads the client axis beyond ``n_clients`` (the ragged-axis
    form): padding rows get zero-filled x and never update — a cohort
    schedule's eligibility mask keeps them out of mixing and
    :func:`step` freezes them in place — so one compiled program serves
    any effective ``n <= n_max``.

    ``compress`` — a :class:`~repro.core.compression.CompressionSpec` or a
    schedule carrying one — allocates the CHOCO error-feedback memory
    (zeroed ``xhat``/``s`` pair per mixed variable) on ``state.comm``;
    ``None`` (and a ``kind="none"`` spec) leave ``comm = ()`` so the carry
    is unchanged.
    """
    if n_max is not None and n_max < n_clients:
        raise ValueError(f"n_max={n_max} < n_clients={n_clients}")
    x = params if stacked else _broadcast_clients(params, n_clients)
    if n_max is not None and n_max > n_clients:
        pad = n_max - n_clients
        x = jax.tree_util.tree_map(
            lambda v: jnp.concatenate(
                [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)]), x)
    spec = (compress if isinstance(compress, CompressionSpec)
            else active_compression(compress) if compress is not None
            else None)
    comm = ({"x": comm_memory(x), "y": comm_memory(x)}
            if spec is not None and spec.kind != "none" else ())
    # one buffer per variable: a round program may donate the whole state
    return DepositumState(x=x, y=_zeros_like(x), nu=_zeros_like(x),
                          mu=_zeros_like(x), g=_zeros_like(x),
                          t=jnp.zeros((), jnp.int32), comm=comm)


GradFn = Callable[[PyTree, Any], tuple[PyTree, Any]]
# grad_fn(x_stacked, batch) -> (g_stacked, aux)


def step(
    state: DepositumState,
    batch: Any,
    grad_fn: GradFn,
    config: DepositumConfig,
    mixer: Any,
    *,
    is_comm_step: jnp.ndarray | bool | None = None,
    hyper: Hyper | None = None,
    active_mask: jnp.ndarray | None = None,
    client_shards: Any = None,
) -> tuple[DepositumState, Any]:
    """One DEPOSITUM iteration for all clients.

    ``mixer`` applies W over the client dim.  Communication gating: if
    ``is_comm_step`` is None it is derived from the config's comm_period via
    ``(t+1) % T0 == 0``; a Python bool may be passed by loops that unroll
    local/comm phases statically (preferred under scan: no collective inside
    ``lax.cond``).

    ``hyper`` overrides the config's continuous hyperparameters with traced
    scalars (sweep path); when None they come from the config's floats.
    Per-step validation covers the config-floats path only (pure host
    arithmetic, matching the old ``make_prox`` guard); explicit hypers are
    validated at the sweep boundary (``sweep_run`` / ``local_then_comm_round``)
    to keep traced/stacked values off the per-step hot path.

    ``mixer`` is a :class:`repro.core.mixing.MixPlan` (run as a constant
    schedule; :data:`~repro.core.gossip.identity_mixer` is the identity
    plan), a :class:`repro.core.schedule.MixSchedule`, or a backend-built
    :class:`~repro.core.schedule.ScheduleMixer`; :func:`~repro.core.
    schedule.round_mixer` turns the first two into the third.  The round
    this iteration belongs to is ``t // T0`` — derived from the state's
    iteration counter, so schedules ride through ``lax.scan`` with no
    carry change.

    ``active_mask`` is the cohort gate: an (n,) 0/1 mask under which rows
    with mask 0 are *frozen* — every state variable keeps its previous
    value (``t`` still advances; it is the shared iteration counter).  When
    None and the mixer is a ``cohort`` schedule, this round's mask is
    derived from the schedule's sampler (:func:`schedule_round_mask`);
    round loops compute it once and pass it to every local step.

    ``client_shards`` is the devices' split of the client dim that the
    fused kernels follow (the shard_map backend's mixers carry it as
    ``mixer.client_shards``, the default); None on one device.
    """
    sm = round_mixer(mixer)
    shards = client_shards if client_shards is not None else sm.client_shards
    is_cohort_mixer = sm.schedule.kind == "cohort"
    r = state.t // config.comm_period
    if active_mask is None:
        active_mask = schedule_round_mask(sm, r)
    comm_spec = active_compression(sm)  # this round's active spec, or None
    mixer = _scoped("gossip", lambda tree: sm(tree, r))
    qmix = None            # how the compressed increment q communicates
    key_x = key_y = None
    if comm_spec is not None:
        if not state.comm:
            raise ValueError(
                "the schedule carries an active CompressionSpec but the "
                "state has no error-feedback memory; build it with "
                "init(..., compress=spec)")
        # packed payloads on the wire when the backend supports it, else q
        # rides the same collective the dense variable would
        qmix = (mixer if sm.wire_fn is None else
                _scoped("gossip", lambda tree: sm.wire_fn(tree, r)))
        key_x, key_y = comm_round_keys(comm_spec, r)
    if hyper is None:
        config.validate()
        hp = config.hyper()
    else:
        hp = hyper
    if is_comm_step is None:
        is_comm_step = (state.t + 1) % config.comm_period == 0
    tm = jax.tree_util.tree_map
    # cast scalars to each leaf's dtype so bf16 params stay bf16 (strong f32
    # scalars would otherwise promote the scan carry and change its type)
    c = lambda s, leaf: jnp.asarray(s, leaf.dtype)

    fused_mode = config.fused_mode()
    if fused_mode == "off":
        fused_ok = False
    else:
        fused_ok, why = fused_eligibility(config, state, hp)
        if fused_mode == "require" and not fused_ok:
            raise ValueError(
                f"fused='require' but the fused kernel cannot serve this "
                f"step: {why}")

    # The cohort gate rides *inside* the kernels (frozen rows written back
    # unchanged) whenever that is exactly equivalent to the reference
    # compute-then-select order: on collective-free steps, and on comm steps
    # whose mixing already masks frozen contributions (cohort schedules).
    # A generic mixer with an explicit mask keeps the legacy outer selects,
    # where active rows may read frozen rows' hypothetical updates.
    kernel_mask = None
    if fused_ok and active_mask is not None and (
            is_comm_step is False or is_cohort_mixer):
        kernel_mask = active_mask

    if fused_ok:
        # (1)+(2) in one sweep-major Pallas VMEM pass per leaf:
        # nu' = g*nu + (1-g)*y; x_half = prox_{alpha h}(x - alpha nu').
        # Under the sweep engine's vmap the custom batching rule maps the
        # stacked-config axis onto Pallas grid axis 0 (kernels/prox/ops).
        from repro.kernels.prox.ops import fused_local_update, hyper_param_vec

        hp_vec = hyper_param_vec(hp)
        x_half, nu_next = fused_local_update(
            state.x, state.y, state.nu, hp_vec, kernel_mask,
            kind=config.prox_name, shards=shards)
        mu_next = state.mu
    else:
        with annotate("local_step"):
            # (1) momentum from the tracking variable
            nu_next, mu_next = momentum_update(
                config.momentum, hp.gamma, state.nu, state.mu, state.y
            )

            # (2) proximal descent + (optional) gossip
            x_half = prox_apply(
                config.prox_name,
                tm(lambda p, v: p - c(hp.alpha, p) * v, state.x, nu_next),
                hp.alpha, lam=hp.lam, theta=hp.theta,
            )

    def _gated_choco(half, mem, key):
        """CHOCO exchange honoring the comm gate: returns (out, new_mem).

        Collective-free steps (``is_comm_step=False``) touch neither the
        tree nor the memory; a traced gate selects both (same caveat as
        the dense path: collective-free mixers only).
        """
        if is_comm_step is False:
            return half, mem
        out, new_mem = choco_mix(comm_spec, qmix, half, mem, key)
        if is_comm_step is True:
            return out, new_mem
        sel = lambda new, old: tm(
            lambda a, b: jnp.where(is_comm_step, a, b), new, old)
        return sel(out, half), CommMemory(xhat=sel(new_mem.xhat, mem.xhat),
                                          s=sel(new_mem.s, mem.s))

    if comm_spec is None:
        mem_x = mem_y = None
        if isinstance(is_comm_step, bool):
            x_next = mixer(x_half) if is_comm_step else x_half
        else:
            # traced gate: only valid with collective-free mixers (dense
            # einsum).
            mixed = mixer(x_half)
            x_next = tm(
                lambda a, b: jnp.where(is_comm_step, a, b), mixed, x_half
            )
    else:
        x_next, mem_x = _gated_choco(x_half, state.comm["x"], key_x)

    # (3) fresh minibatch gradients at the *new* iterate
    with annotate("fwd_bwd"):
        g_next, aux = grad_fn(x_next, batch)

    # (4) gradient tracking with step size beta
    if fused_ok:
        from repro.kernels.prox.ops import fused_tracking

        y_half, g_next = fused_tracking(
            state.y, g_next, state.g, hp_vec, kernel_mask, shards=shards)
    else:
        with annotate("local_step"):
            y_half = tm(
                lambda y, gn, go: y + c(hp.beta, y) * (gn - go),
                state.y, g_next, state.g,
            )
    if comm_spec is None:
        if isinstance(is_comm_step, bool):
            y_next = mixer(y_half) if is_comm_step else y_half
        else:
            mixed_y = mixer(y_half)
            y_next = tm(lambda a, b: jnp.where(is_comm_step, a, b), mixed_y,
                        y_half)
    else:
        y_next, mem_y = _gated_choco(y_half, state.comm["y"], key_y)
    comm_next = (state.comm if comm_spec is None
                 else {"x": mem_x, "y": mem_y})

    if active_mask is not None:
        # freeze inactive/padding rows: keep every old value where mask = 0
        # (select, not arithmetic, so active rows keep their bits exactly)
        am = active_mask

        def keep(new, old):
            return tm(
                lambda nw, od: jnp.where(
                    am.reshape(am.shape + (1,) * (nw.ndim - 1)) > 0, nw, od),
                new, old)

        if kernel_mask is not None:
            # nu / g / the pre-mix halves are already frozen in-kernel; only
            # the mixed variables still need the bit-exact post-mix select
            # (cohort mixing preserves frozen rows up to -0.0 + 0.0)
            if is_comm_step is not False:
                x_next = keep(x_next, state.x)
                y_next = keep(y_next, state.y)
        else:
            x_next = keep(x_next, state.x)
            y_next = keep(y_next, state.y)
            nu_next = keep(nu_next, state.nu)
            mu_next = keep(mu_next, state.mu)
            g_next = keep(g_next, state.g)
        if comm_spec is not None and is_comm_step is not False:
            # frozen rows transmitted nothing: their error-feedback memory
            # must not advance either (both backends agree on this select)
            comm_next = {
                "x": CommMemory(
                    xhat=keep(mem_x.xhat, state.comm["x"].xhat),
                    s=keep(mem_x.s, state.comm["x"].s)),
                "y": CommMemory(
                    xhat=keep(mem_y.xhat, state.comm["y"].xhat),
                    s=keep(mem_y.s, state.comm["y"].s)),
            }

    new_state = DepositumState(
        x=x_next, y=y_next, nu=nu_next, mu=mu_next, g=g_next,
        t=state.t + 1, comm=comm_next
    )
    return new_state, aux


def local_then_comm_round(
    state: DepositumState,
    batches: Any,
    grad_fn: GradFn,
    config: DepositumConfig,
    mixer: Any,
    *,
    hyper: Hyper | None = None,
    active_mask: jnp.ndarray | None = None,
) -> tuple[DepositumState, Any]:
    """One FL round = (T0-1) collective-free local steps + 1 gossip step.

    ``batches`` leaves must carry a leading dim of length T0 (one minibatch
    per inner iteration).  The local phase runs under ``lax.scan`` with the
    identity mixer, so no collective appears inside the scan body; the final
    step applies the real mixer.  This is the production-shaped loop.

    ``mixer`` accepts everything :func:`step` does: a plan, a
    :class:`~repro.core.schedule.MixSchedule` or a backend's
    ``ScheduleMixer``, whose per-round plan the comm step selects from
    ``t // T0``.

    For a ``cohort`` schedule the round's active mask is drawn **once**
    here (``r`` is constant within a round) and threaded through every
    local step and the comm step, freezing inactive and padding rows for
    the whole round; ``active_mask`` overrides the draw.
    """
    T0 = config.comm_period
    if hyper is not None:
        config.validate(hyper)  # once per round; no-op for traced values
    mixer = round_mixer(mixer)
    if active_mask is None:
        active_mask = schedule_round_mask(mixer, state.t // T0)
    shards = mixer.client_shards

    def local_body(carry, batch):
        new_state, aux = step(
            carry, batch, grad_fn, config, identity_mixer,
            is_comm_step=False, hyper=hyper, active_mask=active_mask,
            client_shards=shards,
        )
        return new_state, aux

    if T0 > 1:
        local_batches = jax.tree_util.tree_map(lambda b: b[: T0 - 1], batches)
        state, _ = jax.lax.scan(local_body, state, local_batches)
    last_batch = jax.tree_util.tree_map(lambda b: b[T0 - 1], batches)
    state, aux = step(
        state, last_batch, grad_fn, config, mixer,
        is_comm_step=True, hyper=hyper, active_mask=active_mask,
    )
    return state, aux


# ---------------------------------------------------------------------------
# Paper metrics (Definition 3): stationarity s(x, nu_bar)
# ---------------------------------------------------------------------------

def _client_mean(tree, weights: jnp.ndarray | None = None):
    """Mean over the leading client dim; ``weights`` (n,) restricts it to a
    sub-population (the padded-axis form: pass the eligibility mask so
    zero-filled padding rows do not dilute the average).  ``weights=None``
    keeps the exact unweighted reduction (bit-compatible with older runs).
    """
    if weights is None:
        return jax.tree_util.tree_map(lambda v: jnp.mean(v, axis=0), tree)
    denom = jnp.maximum(jnp.sum(weights.astype(jnp.float32)), 1e-12)

    def leaf(v):
        w = (weights / denom).astype(jnp.float32)
        return jnp.einsum("i,i...->...", w, v.astype(jnp.float32)).astype(
            v.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def _sq_norm(tree, weights: jnp.ndarray | None = None) -> jnp.ndarray:
    """Summed squared norm; ``weights`` (n,) masks the leading client dim
    (only for trees whose leaves carry it)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if weights is None:
        return sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    w = weights.astype(jnp.float32)

    def leaf(l):
        sq = jnp.square(l.astype(jnp.float32))
        per_client = jnp.sum(sq.reshape(sq.shape[0], -1), axis=1)
        return jnp.sum(w * per_client)

    return sum(leaf(l) for l in leaves)


def consensus_error(tree, weights: jnp.ndarray | None = None) -> jnp.ndarray:
    """||J v - v||^2 summed over leaves (leading dim = clients).

    ``weights`` restricts both the mean and the sum to a client
    sub-population (eligible rows of a padded axis)."""
    mean = _client_mean(tree, weights)
    diff = jax.tree_util.tree_map(lambda v, m: v - m[None], tree, mean)
    return _sq_norm(diff, weights)


def stationarity_metrics(
    state: DepositumState,
    grad_fns: dict,
    config: DepositumConfig,
    L: float = 1.0,
    *,
    hyper: Hyper | None = None,
    weights: jnp.ndarray | None = None,
) -> dict[str, jnp.ndarray]:
    """Compute the three Definition-3 terms (uses exact grads; eval only).

    ``weights`` is the padded-axis eligibility mask (n,): all means, norms
    and the client count ``n`` reduce over eligible rows only, so padded
    runs report the same numbers their unpadded references would.

    Definition 2 evaluates ``G^alpha(x_i)`` with the **global** gradient
    ``∇f(x_i) = (1/n) Σ_j ∇f_j(x_i)`` at each client iterate, while the
    estimation error compares ``ν̄`` with ``∇̄f(x) = (1/n) Σ_i ∇f_i(x_i)``
    (each client's *local* gradient at its own iterate).  Hence two callbacks:

    grad_fns = {
      "global_at": x_stacked -> ∇f evaluated at each client's x_i,
      "local_at":  x_stacked -> ∇f_i evaluated at x_i,
    }
    """
    hp = config.hyper() if hyper is None else hyper
    tm = jax.tree_util.tree_map
    if weights is None:
        n = jax.tree_util.tree_leaves(state.x)[0].shape[0]
    else:
        n = jnp.sum(weights.astype(jnp.float32))
    global_grads = grad_fns["global_at"](state.x)
    local_grads = grad_fns["local_at"](state.x)

    # G^alpha(x, grad) = (x - prox_{alpha h}(x - alpha grad)) / alpha
    shifted = tm(lambda p, g: p - hp.alpha * g, state.x, global_grads)
    proxed = prox_apply(config.prox_name, shifted, hp.alpha,
                        lam=hp.lam, theta=hp.theta)
    G = tm(lambda p, q: (p - q) / hp.alpha, state.x, proxed)
    prox_grad_sq = _sq_norm(G, weights)

    cons_x = consensus_error(state.x, weights)

    # ∇̄f(x): mean of local grads at x_i
    gbar = _client_mean(local_grads, weights)
    nubar = _client_mean(state.nu, weights)
    est_err = _sq_norm(
        jax.tree_util.tree_map(lambda a, b: a - b, gbar, nubar)
    )
    s = (prox_grad_sq + L ** 2 * cons_x + n * est_err) / n
    return {
        "prox_grad_sq": prox_grad_sq / n,
        "consensus_x": cons_x / n,
        "grad_est_err": est_err,
        "stationarity": s,
        "consensus_y": consensus_error(state.y, weights) / n,
        "consensus_nu": consensus_error(state.nu, weights) / n,
    }
