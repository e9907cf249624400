"""A whole run of the harness at a tiny size on the CPU (the look for a
chip skipped), with the timed path broken underneath: each fault a
training cell can have must come out as ``correct`` false, and the sound
program as true, under the limits of the cells' file.

The faults are planted in the program's round (the function the trainer's
jitted round calls), so the window, the feed and the readings all go
through the broken path: a round that returns its state unchanged; half
of each client's batch left out, the mean taken over the rest; the
exchange between clients left out.  (A token altered where it is produced
is a serving fault; these cells produce no tokens.)
"""
import jax
import pytest

import repro.training.train_loop as train_loop
from bench.run import run_cell
from bench.spec import load_cell
from bench.tests.tiny import tiny_cell

REAL_ROUND = train_loop.local_then_comm_round


def unchanged(state, batches, grad_fn, config, mixer, **kw):
    _, aux = REAL_ROUND(state, batches, grad_fn, config, mixer, **kw)
    return state, aux


def half_batch(state, batches, grad_fn, config, mixer, **kw):
    half = jax.tree_util.tree_map(lambda b: b[:, :, :b.shape[2] // 2],
                                  batches)
    return REAL_ROUND(state, half, grad_fn, config, mixer, **kw)


def no_exchange(state, batches, grad_fn, config, mixer, **kw):
    from repro.core.gossip import identity_mixer

    return REAL_ROUND(state, batches, grad_fn, config, identity_mixer, **kw)


FAULTS = {"sound": None, "unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_fails_the_check(fault, monkeypatch):
    limits = load_cell("mamba2-130m.star4.b4x512").limits
    if FAULTS[fault] is not None:
        monkeypatch.setattr(train_loop, "local_then_comm_round",
                            FAULTS[fault])
    result = run_cell(tiny_cell(limits=limits), 2 ** 31 + 3, 0.5, False,
                      jax.devices()[:1])
    assert result["correct"] is (fault == "sound"), result["checks"]
    assert list(result)[-1] == "checks"
