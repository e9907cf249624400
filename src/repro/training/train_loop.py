"""Federated training loop: DEPOSITUM x model zoo x data pipeline.

One *round* = T0-1 collective-free local iterations + 1 gossip iteration,
compiled as a single jitted function (``local_then_comm_round``).  Per-client
gradients come from ``jax.vmap(jax.grad(model.loss))`` over the leading client
dim, so the same loop drives a linear model and any zoo architecture.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DepositumConfig,
    DepositumState,
    init as dep_init,
    local_then_comm_round,
    stationarity_metrics,
)
from repro.core.mixing import MixPlan, validate_plan
from repro.core.schedule import MixSchedule, validate_schedule
from repro.launch.steps import make_value_grad_fn
from repro.models.registry import Model
from repro.obs.metrics import round_values
from repro.obs.record import Telemetry
from repro.obs.trace import profile_capture, span
from repro.training.backends import ExecutionBackend, suggest_backend


@dataclasses.dataclass
class TrainerConfig:
    n_clients: int = 10
    topology: str = "ring"
    depositum: DepositumConfig = dataclasses.field(default_factory=DepositumConfig)
    seed: int = 0
    log_every: int = 10


class FederatedTrainer:
    """Drives DEPOSITUM rounds for a zoo model on stacked client batches.

    Clients mix by ``schedule`` (:class:`~repro.core.schedule.MixSchedule`
    — time-varying topologies, partial participation, per-round ``cohort``
    sampling over a padded client axis, Chebyshev rounds), else by the
    plan of ``cfg.topology``, which runs as a constant schedule; the
    backend builds the one mixer from it.  For a ``cohort`` schedule
    ``cfg.n_clients`` is the *padded* axis length ``n_max`` (the round
    program freezes inactive and padding rows).  With ``backend=None`` the
    execution backend is auto-selected from the plan's sparsity and the
    host's devices (:func:`~repro.training.backends.suggest_backend`):
    single-device hosts keep the stacked-vmap simulation, multi-device
    hosts get the matching shard_map collective schedule.
    """

    def __init__(self, model: Model, cfg: TrainerConfig,
                 backend: ExecutionBackend | None = None,
                 schedule: MixSchedule | None = None,
                 telemetry: Telemetry | bool | None = None):
        self.model = model
        self.cfg = cfg
        plan = MixPlan.from_topology(cfg.topology, cfg.n_clients)
        validate_plan(plan, cfg.n_clients)
        self.plan = plan
        self.W = np.asarray(plan.W)
        self.schedule = schedule
        if schedule is not None:
            if (schedule.kind == "cohort"
                    and schedule.sampler.n_max != cfg.n_clients):
                raise ValueError(
                    f"cohort schedule pads to n_max="
                    f"{schedule.sampler.n_max} but cfg.n_clients="
                    f"{cfg.n_clients}; the trainer's client axis must be "
                    "the padded length")
            validate_schedule(schedule, cfg.n_clients)
        operand = schedule if schedule is not None else plan
        self._mix_operand = operand
        backend = backend or suggest_backend(operand, cfg.n_clients)
        self.backend = backend
        self.mixer = backend.mixer_for(operand)

        # shared with AsyncTrainer (same gradient program ⇒ the async τ=0
        # sync-equivalence pin compares trajectories bit for bit)
        grad_fn = make_value_grad_fn(model)
        self._grad_fn = grad_fn
        #: times this trainer's round program was traced (at trace time,
        #: so free at run time): above 1, a round recompiled, e.g. for a
        #: batch of another shape
        self.round_traces = 0

        def round_fn(state, batches):
            self.round_traces += 1
            return local_then_comm_round(state, batches, grad_fn,
                                         cfg.depositum, self.mixer)

        # the round consumes its input state (donated): at model widths two
        # live copies of every client's x, y, nu, mu and g do not fit
        self._round = jax.jit(round_fn, donate_argnums=0)

        if telemetry is True:
            telemetry = Telemetry.memory()
        self.telemetry = telemetry or None
        if self.telemetry is not None:
            tel = self.telemetry

            def round_tel(state, batches, carry, log_every, force):
                self.round_traces += 1
                state, aux = local_then_comm_round(
                    state, batches, grad_fn, cfg.depositum, self.mixer)
                r = (state.t - 1) // cfg.depositum.comm_period
                vals = round_values(state, cfg.depositum,
                                    mixer=self._mix_operand,
                                    aux=aux, n=cfg.n_clients)
                carry = tel.record_and_emit(carry, vals, r, log_every,
                                            force=force)
                return state, aux, carry

            # telemetry reads the post-round state and writes only its own
            # carry: state trajectories are bit-identical to metrics-off.
            # log_every / force are traced operands — cadence toggles
            # cannot recompile (pinned by tests/test_obs.py).
            self._round_tel = jax.jit(round_tel, donate_argnums=0)

    def init_state(self, key) -> DepositumState:
        """Initial state, placed where the backend runs the round."""
        params, _axes = self.model.init(key)
        return self.backend.place(dep_init(params, self.cfg.n_clients))

    def lower_round(self, state: DepositumState, batches):
        """Lower the round program for these shapes (no telemetry).

        ``.compile()`` on the result compiles ahead of time, and later
        rounds of :meth:`run` with the same shapes reuse that executable;
        its ``as_text()`` and ``memory_analysis()`` describe the device
        program."""
        return self._round.lower(state, batches)

    def _logged_rounds(self, n_rounds: int) -> list[int]:
        """Explicit cadence: 1-based rounds that land in history — every
        ``log_every``-th plus always the final one (previously the final
        round was the *only* guaranteed record and intermediate rounds off
        cadence vanished silently)."""
        le = max(1, self.cfg.log_every)
        rounds = [r for r in range(1, n_rounds + 1) if r % le == 0]
        if n_rounds >= 1 and n_rounds not in rounds:
            rounds.append(n_rounds)
        return rounds

    def run(
        self,
        state: DepositumState,
        batch_iter: Iterator[Any],
        n_rounds: int,
        eval_fn: Optional[Callable[[DepositumState, int], dict]] = None,
        *,
        profile_dir: Optional[str] = None,
    ) -> tuple[DepositumState, list[dict]]:
        """batch_iter yields pytrees with leaves (T0, n_clients, B, ...).

        ``state`` is consumed (its buffers are donated to the round
        program); continue from the returned state.

        History has one record per :meth:`_logged_rounds` entry with
        ``round``, ``wall_s``, ``loss`` (the model's scalar loss aux,
        ``ce`` when available) and any ``eval_fn`` keys; with telemetry
        attached, the recorded metric streams (consensus errors,
        prox-gradient norm, bytes-on-wire, ...) merge in by round.
        ``profile_dir`` opts into a ``jax.profiler.trace`` capture of the
        whole loop.  Under any profiler capture the loop records host
        spans: ``trainer.round`` (arg ``round``, 1-based) over each round,
        with children ``trainer.next_batch`` (``next(batch_iter)``),
        ``trainer.dispatch`` (the jitted round call) and, on logged rounds,
        ``trainer.log_sync`` (loss readback and ``eval_fn``); then
        ``trainer.drain`` over the final wait for the state.
        """
        tel = self.telemetry
        logged = set(self._logged_rounds(n_rounds))
        history: list[dict] = []
        by_round: dict[int, dict] = {}
        t0 = time.perf_counter()
        carry = tel.init_carry() if tel is not None else None
        with profile_capture(profile_dir, enabled=profile_dir is not None):
            for r in range(1, n_rounds + 1):
                with span("trainer.round", round=r):
                    with span("trainer.next_batch", round=r):
                        batches = next(batch_iter)
                    with span("trainer.dispatch", round=r):
                        if tel is None:
                            state, aux = self._round(state, batches)
                        else:
                            state, aux, carry = self._round_tel(
                                state, batches, carry, self.cfg.log_every,
                                r == n_rounds)
                    if r in logged:
                        with span("trainer.log_sync", round=r):
                            rec = self._record(r, time.perf_counter() - t0,
                                               aux, state, eval_fn)
                        by_round[r] = rec
                        history.append(rec)
            with span("trainer.drain"):
                jax.block_until_ready(state)
                if tel is not None:
                    tel.sync()
        if tel is not None:
            for event in tel.events(0):
                rec = by_round.get(event["round"])
                if rec is not None:
                    rec.update((k, v) for k, v in event.items()
                               if k not in ("config", "round"))
        return state, history

    @staticmethod
    def _record(r, wall_s, aux, state, eval_fn) -> dict:
        """A logged round's history record; reads the loss back from the
        device (a sync with the round program)."""
        rec = {"round": r, "wall_s": wall_s}
        loss = None
        if isinstance(aux, dict):
            loss = aux.get("ce", aux.get("loss"))
        if loss is not None:
            rec["loss"] = float(jnp.mean(loss))
        if eval_fn is not None:
            rec.update(eval_fn(state, r))
        return rec

    def mean_params(self, state: DepositumState):
        """Consensus (client-averaged) model for evaluation/serving."""
        return jax.tree_util.tree_map(lambda v: jnp.mean(v, axis=0), state.x)


def lm_batch_iterator(stream, trainer_cfg: TrainerConfig, batch: int,
                      seq_len: int) -> Iterator[dict]:
    """Yields {"tokens","labels"} with leaves (T0, n, B, L) from a token stream."""
    T0 = trainer_cfg.depositum.comm_period
    step = 0
    while True:
        block = stream.stacked_round(step, T0, batch, seq_len)  # (T0,n,B,L+1)
        step += T0
        yield {
            "tokens": jnp.asarray(block[..., :-1]),
            "labels": jnp.asarray(block[..., 1:]),
        }


def classification_batch_iterator(dataset, trainer_cfg: TrainerConfig,
                                  batch: int, seed: int = 0) -> Iterator[dict]:
    """Yields {"x","y"} with leaves (T0, n, B, ...) from a labelled dataset."""
    T0 = trainer_cfg.depositum.comm_period
    rng = np.random.default_rng(seed)
    while True:
        xs, ys = dataset.stacked_batches(rng, batch, T0)
        yield {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
