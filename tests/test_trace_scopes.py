"""The round program's device scopes, read where a TPU trace reads them.

A device profile names each op by its HLO instruction; the instruction's
``op_name`` metadata carries the ``jax.named_scope`` path the program put
around it.  On a tiny Mamba-2 round (fused kernels in interpret mode, a
dense star mix) the compiled HLO must show: the model's matmuls and
convolutions under ``fwd_bwd``, the kernel calls under ``fused_kernel``,
the mix under ``gossip``, no instruction under two phases, and no scope
outside :data:`repro.obs.trace.PHASES`.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import DepositumConfig
from repro.models import build_model
from repro.obs.trace import PHASES
from repro.training.train_loop import FederatedTrainer, TrainerConfig

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _phases(op_name: str) -> set:
    # XLA joins the op_names of merged instructions with ";"
    return set(re.split(r"[/;]", op_name)) & set(PHASES)


@pytest.fixture(scope="module")
def round_program():
    """(instructions, scope names entered while tracing) of the compiled
    round: [(opcode, op_name)] for every instruction that carries one."""
    cfg = dataclasses.replace(get_config("mamba2-130m", reduced=True),
                              n_layers=1, d_model=64, vocab_size=256,
                              remat=True, dtype="bfloat16")
    tc = TrainerConfig(
        n_clients=4, topology="star",
        depositum=DepositumConfig(alpha=0.02, comm_period=2,
                                  prox_name="l1", prox_kwargs={"lam": 1e-5},
                                  fused="require"))
    trainer = FederatedTrainer(build_model(cfg), tc)
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 4, 2, 65), jnp.int32)
    batch = {"tokens": tokens[..., :-1], "labels": tokens[..., 1:]}

    entered = []
    named_scope = jax.named_scope

    def spy(name):
        entered.append(name)
        return named_scope(name)

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "named_scope", spy)
    try:
        lowered = trainer.lower_round(state, batch)
    finally:
        mp.undo()
    instrs = []
    for line in lowered.compile().as_text().splitlines():
        m, meta = _INSTR.match(line), _OP_NAME.search(line)
        if m and meta:
            instrs.append((m.group(2), meta.group(1)))
    return instrs, entered


def test_model_matmuls_sit_under_fwd_bwd(round_program):
    instrs, _ = round_program
    # the dense mix is the only matmul outside the model; XLA passes that
    # rebuild an instruction (on the CPU: convolution canonicalisation)
    # may drop its metadata, which leaves it with no scope at all
    mm = [(opc, on) for opc, on in instrs if opc in ("dot", "convolution")]
    model = [on for opc, on in mm if "gossip" not in _phases(on)]
    assert any(opc == "convolution" for opc, _ in mm)
    assert len(model) >= 10
    assert all(_phases(on) == {"fwd_bwd"} for on in model), [
        on for on in model if _phases(on) != {"fwd_bwd"}]


def test_kernels_and_mix_sit_under_their_phases(round_program):
    instrs, _ = round_program
    kernel = [on for _, on in instrs if "_sweep_pallas" in on]
    assert kernel and all(_phases(on) == {"fused_kernel"} for on in kernel)
    # the star's dense W contracts the client axis: einsum "ij,j...->i..."
    mix = [on for _, on in instrs if "ij,j...->i..." in on]
    assert mix and all(_phases(on) == {"gossip"} for on in mix)


def test_phases_are_disjoint_and_the_only_scopes(round_program):
    instrs, entered = round_program
    assert all(len(_phases(on)) <= 1 for _, on in instrs), [
        on for _, on in instrs if len(_phases(on)) > 1]
    seen = set().union(*(_phases(on) for _, on in instrs))
    assert {"fwd_bwd", "fused_kernel", "gossip"} <= seen
    assert entered and set(entered) <= set(PHASES), set(entered)
