"""The control: the reference in float8 e4m3 (operands and state), put in
the program's place, must come out as not correct under the cells'
limits, while the reference itself reads 0 against itself."""
import jax
import pytest

from bench import check, traffic
from bench.reference.depositum import ReferenceRun
from bench.spec import load_cell
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 6])
def test_control_is_not_correct(seed):
    cell = tiny_cell()
    tr = cell.traffic
    rows = traffic.make_rounds(tr, cell.model["vocab_size"], seed, 0,
                               tr["check_rounds"])
    devices = jax.devices()[:1]
    init = cell.weights()
    ref = ReferenceRun(cell.model, tr, devices, init).run(seed, rows)
    same = check.compare(ref, ref)
    assert all(same[k] == 0 for k in check.NUMBERS)
    control = ReferenceRun(cell.model, tr, devices, init,
                           numerics="control").run(seed, rows)
    limits = load_cell("mamba2-130m.star4.b4x512").limits
    ok, checks = check.verdict(check.compare(control, ref), limits)
    assert not ok, checks
