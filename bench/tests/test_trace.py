"""The reduction from a trace to per-layer metrics, on a hand-built trace
whose answers are worked out below, and on a small recorded TPU trace."""
import gzip
import os
import tempfile
from pathlib import Path

import numpy as np
import pytest

from bench import context as ctx_mod
from bench.context import Context
from bench.spec import metric_reader, peaks
from bench.trace import (DeviceTrace, Trace, idle_gaps, read_xplane,
                         scope_map, union_length, _leaf_mask)

MS = 1e6  # ns


def test_union_length_merges_overlaps_and_clips():
    s = np.array([0, 1, 5, 6, 20]) * MS
    e = np.array([2, 3, 8, 7, 30]) * MS
    # [0,3) + [5,8) + [20,30) = 3 + 3 + 10 ms
    assert union_length(s, e) == pytest.approx(16 * MS)
    # clipped to [2, 25): [2,3) + [5,8) + [20,25)
    assert union_length(s, e, 2 * MS, 25 * MS) == pytest.approx(9 * MS)
    assert union_length(s[:0], e[:0]) == 0.0


def test_idle_gaps_and_containers():
    s = np.array([0, 1, 5, 20]) * MS
    e = np.array([4, 2, 8, 30]) * MS
    assert idle_gaps(s, e, 0, 32 * MS) == [(4 * MS, 5 * MS), (8 * MS, 20 * MS),
                                          (30 * MS, 32 * MS)]
    # the first event encloses the second: a container, not a leaf
    assert _leaf_mask(s, e).tolist() == [False, True, True, True]


HLO = """HloModule jit__lambda, entry_computation_layout={...}
  %fused_update_sweep_pallas.3 = (bf16[1,4,8,128]{3,2,1,0}) custom-call(bf16[1,4,8,128]{3,2,1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/while/body/fused_kernel/pallas_call" source_file="x.py"}
  ROOT %collective-permute-start.1 = (f32[1,8]{1,0}) collective-permute-start(f32[1,8]{1,0} %y), metadata={op_name="jit(<lambda>)/gossip/shard_map/ppermute"}
  %fusion.7 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %a), kind=kLoop, calls=%fc, metadata={op_name="jit(<lambda>)/while/body/jvp(mamba)/dot_general"}
  %copy.2 = f32[4,8]{1,0} copy(f32[4,8]{1,0} %b)
"""


def test_scope_map_reads_op_names():
    m = scope_map(HLO)
    assert m["fused_update_sweep_pallas.3"].endswith("fused_kernel/pallas_call")
    assert "gossip" in m["collective-permute-start.1"]
    assert m["fusion.7"].endswith("dot_general")
    assert m["copy.2"] == ""


def hand_trace():
    """One device, window [0, 100) ms, two round programs [0, 40) and
    [45, 95) with a 2 ms op of another program at [41, 43):

    round 1: while [0, 40) enclosing fusion.7 [0, 30), the update kernel
    [30, 36) and a gossip permute [36, 40);
    round 2: while [45, 95) enclosing fusion.7 [45, 85), the update kernel
    [85, 91) and the permute [91, 95).
    """
    ops = [(0, 40, "while.1"), (0, 30, "fusion.7"),
           (30, 36, "fused_update_sweep_pallas.3"),
           (36, 40, "collective-permute-start.1"), (41, 43, "copy.2"),
           (45, 95, "while.1"), (45, 85, "fusion.7"),
           (85, 91, "fused_update_sweep_pallas.3"),
           (91, 95, "collective-permute-start.1")]
    s = np.array([o[0] for o in ops], float) * MS
    e = np.array([o[1] for o in ops], float) * MS
    dev = DeviceTrace(s, e, [o[2] for o in ops], _leaf_mask(s, e),
                      [("jit__lambda(1)", 0, 40 * MS),
                       ("jit__mean(2)", 41 * MS, 43 * MS),
                       ("jit__lambda(1)", 45 * MS, 95 * MS)])
    trace = Trace([dev], [("bench.window", 0.0, 100 * MS),
                          ("bench.next_batch", 42.5 * MS, 44.5 * MS)],
                  scope_map(HLO))
    return Context(trace=trace, rounds=2, comm_period=2, tokens=2000,
                   chips=1, peaks=peaks("TPU v5 lite"),
                   model={"family": "ssm", "n_layers": 1, "d_model": 8,
                          "ssm_expand": 2, "ssm_state": 4,
                          "ssm_head_dim": 4, "ssm_chunk": 8,
                          "vocab_size": 16, "vocab_pad_multiple": 16},
                   seq_len=8, client_leaf_bytes=[1000, 24],
                   clients_per_device=4)


def test_metrics_on_hand_trace():
    ctx = hand_trace()
    read = lambda name: metric_reader(name)(ctx)
    # busy: [0,40) + [41,43) + [45,95) = 92 of 100 ms
    assert read("device_idle_share") == pytest.approx(8.0)
    # one gap [40, 45) between the rounds, 2 ms of it busy with copy.2
    assert read("inter_round_idle_ms") == pytest.approx(3.0)
    # update: 6 + 6 ms over 2 rounds x 2 steps
    assert read("update_ms_per_step") == pytest.approx(3.0)
    # 10 sweeps x 4 clients x 1024 B per 3 ms step, over 819 GB/s
    assert read("update_roofline") == pytest.approx(
        100 * 40960 / 3e-3 / 819e9)
    # gossip: 4 + 4 ms over 2 rounds
    assert read("gossip_ms_per_round") == pytest.approx(4.0)
    # 2000 tokens in 0.1 s at train FLOPs/token over one chip's peak
    from bench.flops import ssm
    fpt = ssm.train_flops_per_token(ctx.model, 8)
    assert read("step_mfu") == pytest.approx(100 * fpt * 2e4 / 197e12)
    # the longest idle gap [5 ms at 95..100) is under no host span but the
    # window; the gap [43, 45) lies under bench.next_batch
    gaps = ctx_mod.idle_gaps_by_host_span(ctx)
    assert gaps[0] == ("bench.window", pytest.approx(5e-3))
    assert ("bench.next_batch", pytest.approx(2e-3)) in gaps
    ops = dict(ctx.device_ops())
    assert ops["fusion body/jvp(mamba)/dot_general"] == \
        pytest.approx(0.07)
    assert "while" not in " ".join(k.split()[0] for k in ops)


def test_readers_return_nothing_without_their_ops():
    ctx = hand_trace()
    ctx.trace.scopes = {}
    for name in ("update_ms_per_step", "update_roofline",
                 "gossip_ms_per_round"):
        assert metric_reader(name)(ctx) is None


DATA = Path(__file__).parent / "data"


@pytest.mark.skipif(not (DATA / "tiny.xplane.pb.gz").exists(),
                    reason="recorded trace not present")
def test_recorded_tpu_trace():
    """A trace recorded on a v5e by record_trace.py: 3 rounds of the tiny
    cell (4 clients, T0 2, fused kernels)."""
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
    dev = trace.devices[0]
    lo, hi = trace.window()
    assert [n for n, s, e in dev.modules
            if lo <= s < hi].count(ctx.round_module(dev)) == 3
    busy = ctx_mod.busy_seconds(ctx)[0]
    assert 0 < busy < ctx.window_s
    # the same union, merged one interval at a time
    merged, total = None, 0.0
    for s, e in sorted(zip(dev.start, dev.end)):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[1]:
            merged[1] = max(merged[1], e)
        else:
            total += merged[1] - merged[0] if merged else 0.0
            merged = [s, e]
    total += merged[1] - merged[0]
    assert busy == pytest.approx(total * 1e-9)
    # each gap between rounds: its length less what other programs ran
    runs = [(s, e) for n, s, e in dev.modules
            if n == ctx.round_module(dev) and lo <= s < hi]
    gaps = [(b - a) - union_length(dev.start, dev.end, a, b)
            for (_, a), (b, _) in zip(runs[:-1], runs[1:])]
    assert metric_reader("inter_round_idle_ms")(ctx) == pytest.approx(
        np.mean(gaps) * 1e-6)
    # the Pallas update kernels carry the fused_kernel scope
    kern = [n for n in dev.names if n.startswith("fused_update_sweep_pallas")]
    assert kern and all("fused_kernel" in scopes[n] for n in kern)
    assert metric_reader("update_ms_per_step")(ctx) > 0
    assert metric_reader("inter_round_idle_ms")(ctx) >= 0
