"""The plain reference against the program at a reduced size on the CPU,
and the traffic generator's law."""
import jax
import numpy as np
import pytest

from bench import check, traffic
from bench.program import Program, batch_feed, import_program
from bench.reference.depositum import ReferenceRun, mixing_matrix
from bench.run import checked_rounds
from bench.tests.tiny import HYBRID, MAMBA, tiny_cell

import_program()


def readings(cell, seed):
    devices = jax.devices()[:1]
    tr = cell.traffic
    rows = traffic.make_rounds(tr, cell.model["vocab_size"], seed, 0,
                               tr["check_rounds"])
    init = cell.weights()
    prog = Program(cell, devices, init)
    state = prog.init_state(seed)
    _, got = checked_rounds(prog, state,
                            batch_feed(rows, jax.profiler.TraceAnnotation),
                            seed)
    ref = ReferenceRun(cell.model, tr, devices, init).run(seed, rows)
    return got, ref


# In float32 the program and the reference do the same arithmetic in
# another order (chunked SSD against the sequential recurrence, fused
# kernels against jnp): they agree to float32 rounding, which the bounds
# below leave a factor of 20 or more above what was read (loss 0 to 2e-7,
# first gradient 4e-7, change 4e-5).
@pytest.mark.parametrize("model", [MAMBA, HYBRID], ids=["ssm", "hybrid"])
def test_program_matches_reference_in_float32(model):
    got, ref = readings(tiny_cell(model, dtype="float32"), 2 ** 31 + 11)
    numbers = check.compare(got, ref)
    assert numbers["loss_gap"] < 1e-5
    assert numbers["grad_norm_gap"] < 1e-5
    assert numbers["change_gap"] < 1e-3


@pytest.mark.parametrize("topology,row0,row1", [
    ("ring", [1 / 3, 1 / 3, 0, 1 / 3], [1 / 3, 1 / 3, 1 / 3, 0]),
    ("star", [1 / 4] * 4, [1 / 4, 3 / 4, 0, 0]),
    ("complete", [1 / 4] * 4, [1 / 4] * 4),
])
def test_metropolis_matrices(topology, row0, row1):
    W = mixing_matrix(topology, 4)
    assert np.allclose(W, W.T) and np.allclose(W.sum(1), 1)
    assert np.allclose(W[0], row0) and np.allclose(W[1], row1)


def test_traffic_is_drawn_from_the_seed():
    tr = tiny_cell().traffic
    a = traffic.make_rounds(tr, 500, 2 ** 32 + 5, 0, 3)
    b = traffic.make_rounds(tr, 500, 2 ** 32 + 5, 1, 2)
    c = traffic.make_rounds(tr, 500, 6, 0, 3)
    assert a.shape == (3, 2, 4, 2, 65) and a.dtype == np.int32
    assert np.array_equal(a[1:], b)          # round r depends on (seed, r)
    assert not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 500
    # every row differs, and each client ranks the vocabulary its own way
    flat = a.reshape(-1, 65)
    assert len({r.tobytes() for r in flat}) == len(flat)
    perms = traffic.client_permutations(500, 4, 2 ** 32 + 5)
    assert len({p.tobytes() for p in perms}) == 4
    assert traffic.round_tokens(tr) == 2 * 4 * 2 * 64


def test_zipf_law():
    tr = dict(tiny_cell().traffic, batch=64, seq_len=511)
    rows = traffic.make_rounds(tr, 500, 3, 0, 1)[0, :, 0]
    perm = traffic.client_permutations(500, 4, 3)[0]
    rank = np.argsort(perm)[rows.ravel()]     # token id -> Zipf rank
    share0 = np.mean(rank == 0)
    cdf = traffic.zipf_cdf(500, 1.2)
    assert share0 == pytest.approx(cdf[0], rel=0.1)
