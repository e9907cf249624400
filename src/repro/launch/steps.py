"""Step builders shared by the real launchers and the dry-run.

* ``build_train_step``  — one full DEPOSITUM iteration (momentum + prox +
  gossip + fresh grads + tracking) for all clients: the communication-round
  step, i.e. the worst case for collectives.
* ``build_local_step``  — the collective-free local iteration (t not in T).
* ``build_serve_step``  — one-token decode against the sharded cache.
* ``build_prefill_step`` — full-context forward materialising the cache.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core import DepositumConfig, identity_mixer
from repro.core.depositum import step as depositum_step
from repro.core.mixing import MixPlan
from repro.core.schedule import round_mixer
from repro.models.registry import Model

if TYPE_CHECKING:  # repro.training imports this module
    from repro.training.backends import ExecutionBackend


def make_grad_fn(model: Model, microbatch: int = 1):
    """Per-client gradients; optional gradient-accumulation microbatching.

    With ``microbatch = M > 1`` the per-client batch B is processed as M
    sequential slabs of B/M under ``lax.scan``, averaging gradients — exact
    (full-batch mean) but with activation temp memory cut ~M-fold.  This is
    the capacity lever for the giant-MoE training shapes (EXPERIMENTS §Perf
    #3b).
    """
    grad_one = jax.grad(lambda p, b: model.loss(p, b), has_aux=True)

    if microbatch <= 1:
        def grad_fn(x_stacked, batch):
            g, aux = jax.vmap(grad_one)(x_stacked, batch)
            return g, aux

        return grad_fn

    def grad_client(params, batch):
        def slab(b):
            return jax.tree_util.tree_map(
                lambda v: v.reshape((microbatch, v.shape[0] // microbatch)
                                    + v.shape[1:]), b)

        def body(acc, mb):
            g, aux = grad_one(params, mb)
            acc = jax.tree_util.tree_map(lambda a, gg: a + gg, acc, g)
            return acc, aux

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        gsum, auxs = jax.lax.scan(body, zeros, slab(batch))
        g = jax.tree_util.tree_map(lambda v: v / microbatch, gsum)
        aux = jax.tree_util.tree_map(lambda v: v[-1], auxs)
        return g, aux

    def grad_fn(x_stacked, batch):
        return jax.vmap(grad_client)(x_stacked, batch)

    return grad_fn


def make_value_grad_fn(model: Model):
    """Per-client gradients with the scalar loss joined into the aux.

    ``value_and_grad``, not ``grad``: the per-client loss lands in the aux
    (``{"loss": ...}``) so history/telemetry always have one even when the
    model's own aux carries no ``"ce"``.  Gradients — hence trajectories —
    are bit-identical to :func:`make_grad_fn`'s (``grad`` IS
    ``value_and_grad`` with the value dropped).  Shared by
    ``FederatedTrainer`` and ``AsyncTrainer`` so the synchronous scan and
    the async driver run the *same* gradient program — the τ=0
    sync-equivalence pin compares their trajectories bit for bit.
    """
    vg_one = jax.value_and_grad(lambda p, b: model.loss(p, b),
                                has_aux=True)

    def grad_fn(x_stacked, batch):
        (loss, aux), g = jax.vmap(vg_one)(x_stacked, batch)
        merged = dict(aux) if isinstance(aux, dict) else {}
        merged.setdefault("loss", loss)
        return g, merged

    return grad_fn


def build_train_step(
    model: Model,
    dep_cfg: DepositumConfig,
    n_clients: int,
    mixer=None,
    *,
    microbatch: int = 1,
    backend: ExecutionBackend | None = None,
):
    """(state, batch) -> (state, aux); batch leaves (n, B, ...).

    ``mixer`` is the one mixing operand: a plan, a
    :class:`~repro.core.schedule.MixSchedule`, or a ``ScheduleMixer`` (such
    as the placement-aware shard_map mixer of ``launch.gossip_dist``);
    default the Metropolis ring plan.  A plan or schedule is executed by
    ``backend`` (default stacked-vmap: dense contraction, which GSPMD
    lowers to all-gather + local einsum on a sharded client axis).
    Schedules derive their round from the state's iteration counter
    (``t // T0``) inside ``depositum.step`` — including ``cohort``
    schedules, whose per-round active mask both gates the mix and freezes
    inactive/padding rows of the (padded) client axis.
    """
    if mixer is None:
        mixer = MixPlan.from_topology("ring", n_clients)
    mixer = round_mixer(mixer, backend)
    grad_fn = make_grad_fn(model, microbatch=microbatch)

    def train_step(state, batch):
        return depositum_step(
            state, batch, grad_fn, dep_cfg, mixer, is_comm_step=True
        )

    return train_step


def build_local_step(model: Model, dep_cfg: DepositumConfig):
    grad_fn = make_grad_fn(model)

    def local_step(state, batch):
        return depositum_step(
            state, batch, grad_fn, dep_cfg, identity_mixer, is_comm_step=False
        )

    return local_step


def build_serve_step(model: Model):
    def serve_step(params, cache, batch):
        logits, new_cache = model.forward_decode(params, batch, cache)
        return logits, new_cache

    return serve_step


def build_prefill_step(model: Model, capacity: int):
    cfg = model.cfg
    if cfg.family == "encdec":
        from repro.models import encdec as encdec_mod

        def prefill_step(params, batch):
            memory = encdec_mod.encode(
                params, batch["frames"], cfg,
                window=cfg.long_context_window,
            )
            # decoder consumes its prompt against the fresh memory
            logits, _ = encdec_mod.forward_train(
                params, {"tokens": batch["tokens"]}, cfg, memory=memory
            )
            return logits[:, -1:, :], memory

        return prefill_step

    def prefill_step(params, batch):
        logits, cache = model.forward_prefill(params, batch, capacity)
        return logits, cache

    return prefill_step
