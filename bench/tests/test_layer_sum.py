"""The forward/backward and unscoped readers, on a hand-built trace whose
answers are worked out below: with the update and the gossip they add up to
all leaf-op device time per step."""
import gzip
import os
import tempfile
from pathlib import Path

import numpy as np
import pytest

from bench.context import Context
from bench.metrics.unscoped_ms_per_step import leaf_seconds
from bench.spec import metric_reader, peaks
from bench.trace import DeviceTrace, Trace, read_xplane, scope_map, _leaf_mask

MS = 1e6  # ns
BODY = "jit(round_fn)/while/body/closed_call"
HLO = f"""HloModule jit_round_fn, entry_computation_layout={{...}}
  %fusion.7 = bf16[4,8]{{1,0}} fusion(bf16[4,8]{{1,0}} %a), kind=kOutput, calls=%fc, metadata={{op_name="{BODY}/fwd_bwd/vmap(jvp())/dot_general"}}
  %convolution.2 = f32[4,8]{{1,0}} convolution(f32[4,8]{{1,0}} %b, f32[4,8]{{1,0}} %c), metadata={{op_name="{BODY}/fwd_bwd/vmap(transpose(jvp()))/checkpoint/conv_general_dilated"}}
  %fused_update_sweep_pallas.3 = (bf16[1,4,8,128]{{3,2,1,0}}) custom-call(bf16[1,4,8,128]{{3,2,1,0}} %p), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/fused_kernel/pallas_call"}}
  %dot.4 = bf16[4,8]{{1,0}} dot(bf16[4,4]{{1,0}} %w, bf16[4,8]{{1,0}} %x), metadata={{op_name="jit(round_fn)/gossip/ij,j...->i.../dot_general"}}
  %reduce-window.5 = f32[4,8]{{1,0}} reduce-window(f32[4,8]{{1,0}} %d, f32[] %z), metadata={{op_name="{BODY}/reduce_window_sum"}}
  %copy.2 = bf16[4,8]{{1,0}} copy(bf16[4,8]{{1,0}} %e)
"""


def hand_trace(scopes=None):
    """One device, window [0, 100) ms, 2 rounds of T0 = 2 steps:

    round 1: while [0, 40) enclosing fusion.7 [0, 20), convolution.2
    [20, 26), the update kernel [26, 32), copy.2 [32, 34), the mix dot.4
    [34, 38), reduce-window.5 [38, 40); another program's reduce.9 at
    [41, 42);
    round 2: while [45, 95) enclosing fusion.7 [45, 70), convolution.2
    [70, 76), the kernel [76, 82), copy.2 [82, 85), dot.4 [85, 90),
    reduce-window.5 [90, 95); fusion.7 again at [100, 105), after the
    window.

    fwd_bwd 20 + 6 + 25 + 6 = 57 ms, update 6 + 6 = 12, gossip 4 + 5 = 9,
    none 2 + 2 + 1 + 3 + 5 = 13: 91 ms of leaf ops over 4 steps.
    """
    ops = [(0, 40, "while.1"), (0, 20, "fusion.7"), (20, 26, "convolution.2"),
           (26, 32, "fused_update_sweep_pallas.3"), (32, 34, "copy.2"),
           (34, 38, "dot.4"), (38, 40, "reduce-window.5"), (41, 42, "reduce.9"),
           (45, 95, "while.1"), (45, 70, "fusion.7"),
           (70, 76, "convolution.2"), (76, 82, "fused_update_sweep_pallas.3"),
           (82, 85, "copy.2"), (85, 90, "dot.4"), (90, 95, "reduce-window.5"),
           (100, 105, "fusion.7")]
    s = np.array([o[0] for o in ops], float) * MS
    e = np.array([o[1] for o in ops], float) * MS
    dev = DeviceTrace(s, e, [o[2] for o in ops], _leaf_mask(s, e),
                      [("jit_round_fn(1)", 0, 40 * MS),
                       ("jit_mean(2)", 41 * MS, 42 * MS),
                       ("jit_round_fn(1)", 45 * MS, 95 * MS),
                       ("jit_round_fn(1)", 100 * MS, 105 * MS)])
    trace = Trace([dev], [("bench.window", 0.0, 100 * MS)],
                  scope_map(HLO) if scopes is None else scopes)
    return Context(trace=trace, rounds=2, comm_period=2, tokens=2000,
                   chips=1, peaks=peaks("TPU v5 lite"), model={}, seq_len=8,
                   client_leaf_bytes=[1000, 24], clients_per_device=4)


def test_fwd_bwd_and_unscoped_on_hand_trace():
    ctx = hand_trace()
    read = lambda name: metric_reader(name)(ctx)
    assert read("fwd_bwd_ms_per_step") == pytest.approx(57 / 4)
    assert read("unscoped_ms_per_step") == pytest.approx(13 / 4)
    assert read("update_ms_per_step") == pytest.approx(12 / 4)
    assert read("gossip_ms_per_round") == pytest.approx(9 / 2)
    assert leaf_seconds(ctx) == [pytest.approx(91e-3)]


def test_layers_add_up_to_all_leaf_op_time():
    ctx = hand_trace()
    read = lambda name: metric_reader(name)(ctx)
    per_step = 1e3 * leaf_seconds(ctx)[0] / (ctx.rounds * ctx.comm_period)
    assert (read("fwd_bwd_ms_per_step") + read("update_ms_per_step")
            + read("gossip_ms_per_round") / ctx.comm_period
            + read("unscoped_ms_per_step")) == pytest.approx(per_step)


def test_nothing_to_read_without_a_fwd_bwd_scope():
    # a program that does not name its forward and backward pass
    no_fwd_bwd = {k: v.replace("/fwd_bwd", "")
                  for k, v in scope_map(HLO).items()}
    for scopes in ({}, no_fwd_bwd):
        ctx = hand_trace(scopes)
        assert metric_reader("fwd_bwd_ms_per_step")(ctx) is None
        assert metric_reader("unscoped_ms_per_step")(ctx) is None


DATA = Path(__file__).parent / "data"


@pytest.mark.skipif(not (DATA / "tiny.xplane.pb.gz").exists(),
                    reason="recorded trace not present")
def test_recorded_trace_of_a_program_without_the_scope():
    """The recorded v5e trace (record_trace.py) predates the ``fwd_bwd``
    scope: both readers return nothing and raise nothing."""
    with gzip.open(DATA / "tiny.hlo.txt.gz", "rt") as f:
        scopes = scope_map(f.read())
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.xplane.pb")
        with gzip.open(DATA / "tiny.xplane.pb.gz", "rb") as f, \
                open(path, "wb") as g:
            g.write(f.read())
        trace = read_xplane(path, 1, scopes)
    ctx = Context(trace=trace, rounds=3, comm_period=2, tokens=3 * 2 * 4 * 128,
                  chips=1, peaks=peaks("TPU v5 lite"), model={},
                  seq_len=64, client_leaf_bytes=[1], clients_per_device=4)
    assert not any("fwd_bwd" in v for v in scopes.values())
    assert metric_reader("fwd_bwd_ms_per_step")(ctx) is None
    assert metric_reader("unscoped_ms_per_step")(ctx) is None
    assert leaf_seconds(ctx)[0] > 0
