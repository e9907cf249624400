"""The system under test: the program's FederatedTrainer for one cell.

Builds the trainer as the program's own launcher would (zoo model,
DEPOSITUM with ``fused="require"``, the cell's topology, backend chosen by
``suggest_backend``), its state on the device from the benchmark's
seeded weights, and the host-to-device batch feed.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.reference import init as ref_init
from bench.reference.depositum import norms
from bench.spec import ROOT, Cell


def import_program(root=ROOT):
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(cell: Cell):
    from repro.configs.base import ModelConfig

    return ModelConfig(name=cell.config["name"], source=cell.config["source"],
                       **cell.config["model"])


def depositum_config(traffic: dict):
    from repro.core import DepositumConfig

    return DepositumConfig(alpha=traffic["alpha"], beta=traffic["beta"],
                           gamma=traffic["gamma"], momentum=traffic["momentum"],
                           comm_period=traffic["comm_period"],
                           prox_name=traffic["prox"],
                           prox_kwargs={"lam": traffic["lam"]},
                           fused="require")


class Program:
    """Trainer, its seeded state and the readings the check needs."""

    def __init__(self, cell: Cell, devices, init):
        from repro.core import MixPlan, MixSchedule
        from repro.models import build_model
        from repro.training.backends import suggest_backend
        from repro.training.train_loop import FederatedTrainer, TrainerConfig

        tr = cell.traffic
        self.cell = cell
        n = tr["n_clients"]
        self.model = build_model(model_config(cell))
        tc = TrainerConfig(n_clients=n, topology=tr["topology"],
                           depositum=depositum_config(tr))
        schedule = None
        if tr["plan"] == "sparse":
            schedule = MixSchedule.constant(
                MixPlan.from_topology(tr["topology"], n, prefer="sparse"))
        operand = schedule if schedule is not None else \
            MixPlan.from_topology(tr["topology"], n)
        backend = suggest_backend(operand, n, devices=devices)
        self.trainer = FederatedTrainer(self.model, tc, schedule=schedule,
                                        backend=backend)
        from repro.core import init as dep_init

        self.init = init
        made = jax.eval_shape(init, jax.random.key(0))
        shardings = jax.tree_util.tree_map(
            self._sharding, jax.eval_shape(lambda p: dep_init(p, n), made))
        self._state = jax.jit(lambda p: dep_init(p, n),
                              out_shardings=shardings)
        self._norms = jax.jit(norms)
        self._change = jax.jit(lambda x, p: norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32)[None],
            x, p)))

    def _sharding(self, leaf):
        backend = self.trainer.backend
        if backend.name == "shard_map":
            spec = P(backend.axis_name) if leaf.ndim else P()
            return NamedSharding(backend.mesh, spec)
        return None

    def init_state(self, seed: int):
        """DEPOSITUM's initial state (every client at the seeded weights),
        made on the device and placed where the backend runs the round."""
        return self._state(self.init(ref_init.seed_key(seed)))

    def leaf_norms(self, tree) -> list[np.ndarray]:
        """Per client and leaf: the norm of ``tree``'s leaf."""
        return [np.asarray(v) for v in
                jax.tree_util.tree_leaves(jax.device_get(self._norms(tree)))]

    def change_norms(self, x, seed: int) -> list[np.ndarray]:
        """Per client and leaf: the norm of x less the seeded weights."""
        out = jax.device_get(self._change(x, self.init(
            ref_init.seed_key(seed))))
        return [np.asarray(v) for v in jax.tree_util.tree_leaves(out)]


def batch_feed(rounds: np.ndarray, annotate):
    """Yields the trainer's batches {"tokens", "labels"} (T0, n, B, L) from
    host token blocks, copying each to the device as it is taken, as the
    program's ``lm_batch_iterator`` does; ``annotate`` names the host span."""
    for block in rounds:
        with annotate("bench.next_batch"):
            batch = {"tokens": jnp.asarray(block[..., :-1]),
                     "labels": jnp.asarray(block[..., 1:])}
        yield batch
