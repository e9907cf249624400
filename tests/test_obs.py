"""Telemetry suite: recorder correctness, byte accounting, retrace pins.

The contracts under test, in order:

* metrics-on trajectories are **bit-exact** with metrics-off ones on the
  stacked-vmap trainer, the sweep engine, and (slow, subprocess) shard_map;
* recorded streams exactly match a post-hoc recompute — both the sweep
  engine's own ``metrics_fn`` outputs at the logged rounds and
  ``stationarity_metrics``'s consensus terms on the final state;
* the traced bytes-on-wire accounting equals :mod:`repro.analysis.comm`
  rule for rule;
* swapping sinks or toggling ``log_every`` does **not** recompile (trace
  counts pinned on both the trainer round and the sweep runner);
* the trainer's history has no silent gaps: off-cadence runs still record
  the final round, and ``loss`` survives models whose aux has no ``"ce"``;
* the trainer's host spans (``trainer.*``) nest inside their round in a
  profiler capture, and ``round_traces`` counts the round program's traces;
* (slow) on a composite quadratic the recorded prox-gradient and
  consensus-error streams are decreasing in running mean — the O(1/T)
  sanity check of Theorem 1.
"""
import glob
import json
import os
import textwrap
from collections import Counter
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.comm import payload_row_bytes, round_wire_bytes
from repro.core import (
    DepositumConfig,
    MixPlan,
    init as dep_init,
    local_then_comm_round,
    stationarity_metrics,
)
from repro.core.compression import CompressionSpec, stack_specs
from repro.core.hyper import Hyper, stack_hypers
from repro.core.schedule import MixSchedule
from repro.obs.metrics import (
    MetricSpec,
    round_values,
    traced_payload_row_bytes,
    traced_round_bytes,
)
from repro.obs.record import Telemetry
from repro.obs.sinks import JsonlSink, MemorySink, validate_event, validate_jsonl
from repro.obs.trace import PHASES
from repro.training.backends import StackedVmapBackend
from repro.training.sweep import _scanned_run, sweep_run
from repro.training.train_loop import FederatedTrainer, TrainerConfig

N, D, T0 = 4, 12, 2


# ---------------------------------------------------------------------------
# Shared problem: per-client least squares (composite with l1 prox)
# ---------------------------------------------------------------------------

def _ls_problem(n=N, d=D, seed=0):
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (n, 16, d)) * 0.3
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, 16))

    def grad_fn(x, batch):
        def one(xi, Ai, bi):
            r = Ai @ xi - bi
            return 2.0 * Ai.T @ r / Ai.shape[0]
        return jax.vmap(one)(x, A, b), {}

    return grad_fn, A, b


def _cfg(**kw):
    kw.setdefault("alpha", 0.05)
    kw.setdefault("comm_period", T0)
    kw.setdefault("prox_name", "l1")
    kw.setdefault("prox_kwargs", {"lam": 1e-4})
    return DepositumConfig(**kw)


def _sched(n=N):
    return MixSchedule.constant(MixPlan.dense(jnp.full((n, n), 1.0 / n)))


def _batches(rounds, n=N):
    return jnp.zeros((rounds, T0, n, 1))


# A minimal zoo-shaped model for trainer tests.  Its loss aux carries NO
# "ce" key, exercising the value_and_grad scalar-loss fallback.
class _ToyModel(NamedTuple):
    cfg: object
    init: object
    forward_train: object
    loss: object
    forward_decode: object
    init_decode_cache: object


def _toy_model(d=D, seed=0, on_trace=None):
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (16, d)) * 0.3
    b = jax.random.normal(jax.random.fold_in(key, 1), (16,))

    def init(key):
        return {"w": jnp.zeros((d,))}, None

    def loss(params, batch):
        if on_trace is not None:
            on_trace()
        r = A @ params["w"] - b
        return jnp.mean(r * r), {}

    return _ToyModel(cfg=None, init=init, forward_train=None, loss=loss,
                     forward_decode=None, init_decode_cache=None)


def _trainer_batches(rounds, n=N):
    def it():
        while True:
            yield jnp.zeros((T0, n, 1))
    return it()


# ---------------------------------------------------------------------------
# MetricSpec / sinks
# ---------------------------------------------------------------------------

def test_metric_spec_validates():
    assert MetricSpec().n_metrics == 9
    with pytest.raises(ValueError):
        MetricSpec(names=("prox_grad_sq", "nope"))
    with pytest.raises(ValueError):
        MetricSpec(buffer=0)


def test_validate_event_rejects_malformed():
    names = ("prox_grad_sq",)
    ok = {"config": 0, "round": 3, "prox_grad_sq": 0.5}
    validate_event(ok, names)
    with pytest.raises(ValueError):
        validate_event({**ok, "round": -1}, names)
    with pytest.raises(ValueError):
        validate_event({**ok, "prox_grad_sq": float("inf")}, names)
    with pytest.raises(ValueError):
        validate_event({"config": 0, "prox_grad_sq": 0.5}, names)


def test_jsonl_sink_roundtrip_and_schema(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    sink.write([{"config": 0, "round": 1, "loss": 0.5},
                {"config": 1, "round": 1, "loss": 0.25}])
    sink.close()
    assert validate_jsonl(path, ("loss",)) == 2
    rows = [json.loads(l) for l in open(path)]
    assert [r["config"] for r in rows] == [0, 1]
    # a malformed line must fail the schema check
    with open(path, "a") as f:
        f.write(json.dumps({"config": 0, "round": 0, "loss": "oops"}) + "\n")
    with pytest.raises(ValueError):
        validate_jsonl(path, ("loss",))


def test_ring_buffer_overflow_recovers_latest_rows():
    """More logged rounds than buffer rows: the host keeps the newest B
    and never double-emits on repeated flushes of the same count."""
    spec = MetricSpec(names=("loss",), buffer=3)
    tel = Telemetry(spec, [MemorySink()])
    carry = tel.init_carry()
    rec = jax.jit(lambda c, v, r: tel.record(c, {"loss": v}, r, 1))
    for r in range(7):
        carry = rec(carry, jnp.float32(r), r)
    tel.emit(carry)
    tel.emit(carry)  # second flush of the same buffer: must be a no-op
    tel.sync()
    events = tel.events(0)
    assert [e["round"] for e in events] == [5, 6, 7]
    assert [e["loss"] for e in events] == [4.0, 5.0, 6.0]


# ---------------------------------------------------------------------------
# Traced bytes accounting == analysis.comm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    None,
    CompressionSpec.none(),
    CompressionSpec.topk(0.1),
    CompressionSpec.topk(0.25, wire_k=7),
    CompressionSpec.randk(0.05),
    CompressionSpec.qsgd(4.0),
])
def test_traced_payload_bytes_match_host(spec):
    for d in (10, 257, 4096):
        got = float(jax.jit(lambda: traced_payload_row_bytes(spec, d))())
        want = float(payload_row_bytes(spec, d))
        assert got == want, (spec and spec.kind, d, got, want)


def test_traced_payload_bytes_mixed_kinds():
    mixed = stack_specs([CompressionSpec.none(),
                         CompressionSpec.topk(0.1),
                         CompressionSpec.qsgd(4.0)])
    d = 128
    got = np.asarray(jax.jit(
        lambda: traced_payload_row_bytes(mixed, d))())
    want = np.asarray(payload_row_bytes(mixed, d))
    np.testing.assert_array_equal(got, want)


def test_traced_round_bytes_match_host():
    d = 64
    ring = MixPlan.from_topology("ring", N)
    cases = [
        (MixSchedule.constant(ring), None),
        (MixSchedule.constant(MixPlan.from_topology("complete", N)), N),
        (MixSchedule.constant(MixPlan.chebyshev(ring, 3)), None),
        (MixSchedule.constant(ring).with_compression(
            CompressionSpec.topk(0.1)), None),
        (MixPlan.from_topology("star", N), None),  # bare plan
    ]
    for sched, n in cases:
        got = float(jax.jit(
            lambda s=sched: traced_round_bytes(s, 0, d, n=n))())
        want = float(round_wire_bytes(sched, d, n=n))
        assert got == want, (sched, got, want)


def test_traced_round_bytes_lazy_counts_drawn_mask():
    """Lazy rounds count the realised per-round graph (analysis.comm with
    an explicit r), not the sampler expectation."""
    d = 32
    sched = MixSchedule.lazy(MixPlan.from_topology("ring", N), 0.5,
                             rounds=6, seed=3)
    for r in range(6):
        got = float(jax.jit(
            lambda rr: traced_round_bytes(sched, rr, d))(jnp.int32(r)))
        want = float(round_wire_bytes(sched, d, r=r))
        assert got == want, (r, got, want)


def test_traced_round_bytes_structureless_mixer_is_nan():
    got = float(traced_round_bytes(lambda t: t, 0, 8))
    assert got != got  # NaN: legacy closures carry no plan structure


# ---------------------------------------------------------------------------
# Bit-exactness: metrics-on vs metrics-off
# ---------------------------------------------------------------------------

def test_trainer_metrics_on_is_bitexact():
    rounds = 5
    cfg = TrainerConfig(n_clients=N, depositum=_cfg(), log_every=2)
    model = _toy_model()
    off = FederatedTrainer(model, cfg, schedule=_sched())
    on = FederatedTrainer(model, cfg, schedule=_sched(),
                          telemetry=Telemetry(MetricSpec(buffer=rounds + 1)))
    key = jax.random.PRNGKey(0)
    s_off, _ = off.run(off.init_state(key), _trainer_batches(rounds), rounds)
    s_on, _ = on.run(on.init_state(key), _trainer_batches(rounds), rounds)
    for a, b in zip(jax.tree_util.tree_leaves(s_off),
                    jax.tree_util.tree_leaves(s_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sweep_metrics_on_is_bitexact():
    rounds = 4
    grad_fn, _, _ = _ls_problem()
    hypers = stack_hypers([Hyper.create(alpha=a, lam=1e-4)
                           for a in (0.03, 0.05)])
    params0 = jnp.zeros((D,))
    kw = dict(n_clients=N, metrics_fn=None)
    s_off, _ = sweep_run(params0, grad_fn, _cfg(), _sched(), hypers,
                         _batches(rounds), **kw)
    tel = Telemetry(MetricSpec(buffer=rounds + 1))
    s_on, _ = sweep_run(params0, grad_fn, _cfg(), _sched(), hypers,
                        _batches(rounds), telemetry=tel, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(s_off),
                    jax.tree_util.tree_leaves(s_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Recorded streams == post-hoc recompute
# ---------------------------------------------------------------------------

def test_recorded_streams_match_posthoc_recompute():
    """Every recorded metric equals the sweep engine's own per-round
    ``metrics_fn`` output at the logged rounds — same computation, recorded
    vs returned — and the final-round consensus terms equal a fresh
    ``stationarity_metrics`` recompute."""
    rounds, log_every = 6, 2
    grad_fn, A, b = _ls_problem()
    cfg = _cfg()
    sched = _sched()
    hypers = stack_hypers([Hyper.create(alpha=a, lam=1e-4)
                           for a in (0.03, 0.05, 0.08)])
    params0 = jnp.zeros((D,))

    def metrics_fn(state, hyper, plan):
        return round_values(state, cfg, hyper=hyper, mixer=plan,
                            aux={}, n=N)

    tel = Telemetry(MetricSpec(buffer=rounds + 1))
    final, outs = sweep_run(params0, grad_fn, cfg, sched, hypers,
                            _batches(rounds), n_clients=N,
                            metrics_fn=metrics_fn, telemetry=tel,
                            log_every=log_every)
    tel.sync()
    logged = [r for r in range(1, rounds + 1)
              if r % log_every == 0 or r == rounds]
    sink = tel.memory_sink
    for s in range(3):
        assert sink.rounds(s) == logged
        for name in MetricSpec().names:
            if name == "loss":
                continue  # aux={} -> NaN stream; compared via isnan below
            rec = np.asarray(sink.stream(name, s), np.float32)
            want = np.asarray(outs[name][s])[np.asarray(logged) - 1]
            np.testing.assert_array_equal(rec, want.astype(np.float32),
                                          err_msg=f"config {s}: {name}")
        assert all(v != v for v in sink.stream("loss", s))

    # consensus terms vs stationarity_metrics on the final state, exactly
    def global_at(x):
        def gi(xi):
            r = jnp.einsum("nkd,d->nk", A, xi) - b
            return jnp.mean(jax.vmap(
                lambda Ai, ri: 2.0 * Ai.T @ ri / Ai.shape[0])(A, r), axis=0)
        return jax.vmap(gi)(x)

    def local_at(x):
        def one(xi, Ai, bi):
            return 2.0 * Ai.T @ (Ai @ xi - bi) / Ai.shape[0]
        return jax.vmap(one)(x, A, b)

    for s in range(3):
        point = jax.tree_util.tree_map(lambda l: l[s], final)
        hp = jax.tree_util.tree_map(lambda l: l[s], hypers)
        sm = jax.jit(lambda st, h: stationarity_metrics(
            st, {"global_at": global_at, "local_at": local_at}, cfg,
            hyper=h))(point, hp)
        for rec_name, sm_name in (("consensus_x", "consensus_x"),
                                  ("consensus_y", "consensus_y"),
                                  ("momentum_var", "consensus_nu")):
            rec = sink.stream(rec_name, s)[-1]
            assert rec == np.float32(sm[sm_name]), (rec_name, s)


# ---------------------------------------------------------------------------
# Zero-retrace pins: sink and cadence toggles reuse the compiled program
# ---------------------------------------------------------------------------

def test_trainer_cadence_and_sink_toggles_do_not_retrace():
    traces = []
    model = _toy_model(on_trace=lambda: traces.append(1))
    cfg = TrainerConfig(n_clients=N, depositum=_cfg(), log_every=1)
    tr = FederatedTrainer(model, cfg, schedule=_sched(),
                          telemetry=Telemetry(MetricSpec(buffer=8)))
    key = jax.random.PRNGKey(0)
    state = tr.init_state(key)
    state, _ = tr.run(state, _trainer_batches(3), 3)
    baseline = sum(traces)
    assert baseline > 0
    tr.cfg.log_every = 2                      # cadence toggle
    tr.telemetry.sinks = [MemorySink()]       # sink swap
    state, _ = tr.run(state, _trainer_batches(3), 3)
    assert sum(traces) == baseline, (
        f"sink/cadence toggle retraced: {sum(traces)} trace events vs "
        f"{baseline} for the first compile")


def test_sweep_cadence_and_sink_toggles_do_not_retrace():
    traces = []
    base, _, _ = _ls_problem()

    def grad_fn(x, batch):
        traces.append(1)
        return base(x, batch)

    cfg = _cfg()
    tel = Telemetry(MetricSpec(buffer=8))
    backend = StackedVmapBackend()
    run_one = _scanned_run(grad_fn, cfg, N, None, backend.mixer_for, tel)
    runner = jax.jit(jax.vmap(run_one,
                              in_axes=(0, None, None, None, 0, None)))
    hypers = stack_hypers([Hyper.create(alpha=a, lam=1e-4)
                           for a in (0.03, 0.05)])
    tags = jnp.arange(2, dtype=jnp.int32)
    batches = _batches(3)
    runner(hypers, _sched(), jnp.zeros((D,)), batches, tags,
           jnp.asarray(1, jnp.int32))
    baseline = sum(traces)
    assert baseline > 0
    tel.sinks = [MemorySink(), MemorySink()]  # sink swap
    for le in (2, 3, 7):                      # cadence toggles
        runner(hypers, _sched(), jnp.zeros((D,)), batches, tags,
               jnp.asarray(le, jnp.int32))
    assert sum(traces) == baseline, (
        f"sink/cadence toggle retraced: {sum(traces)} trace events vs "
        f"{baseline} for the first compile")


# ---------------------------------------------------------------------------
# Trainer history: no silent gaps, loss fallback
# ---------------------------------------------------------------------------

def test_trainer_history_records_final_round_off_cadence():
    """Regression: with log_every=10 and 7 rounds the old loop returned an
    empty history — off-cadence rounds (including the last) vanished."""
    cfg = TrainerConfig(n_clients=N, depositum=_cfg(), log_every=10)
    tr = FederatedTrainer(_toy_model(), cfg, schedule=_sched())
    _, history = tr.run(tr.init_state(jax.random.PRNGKey(0)),
                        _trainer_batches(7), 7)
    assert [h["round"] for h in history] == [7]
    # _toy_model's aux has no "ce": loss comes from the value_and_grad
    # scalar fallback, not a missing key
    assert np.isfinite(history[0]["loss"])


def test_trainer_history_cadence_is_explicit():
    cfg = TrainerConfig(n_clients=N, depositum=_cfg(), log_every=2)
    tr = FederatedTrainer(_toy_model(), cfg, schedule=_sched(),
                          telemetry=True)
    _, history = tr.run(tr.init_state(jax.random.PRNGKey(0)),
                        _trainer_batches(7), 7)
    assert [h["round"] for h in history] == [2, 4, 6, 7]
    for rec in history:
        # telemetry streams merged into the history records by round
        assert "consensus_x" in rec and "wire_bytes" in rec
        assert np.isfinite(rec["loss"])
        assert rec["wire_bytes"] == N * (N - 1) * D * 4 * 2


# ---------------------------------------------------------------------------
# Trainer host spans and the round-trace counter
# ---------------------------------------------------------------------------

TRAINER_CHILDREN = ("trainer.next_batch", "trainer.dispatch",
                    "trainer.log_sync")


def _host_events(log_dir):
    """(name, start_ns, end_ns, args) of every host event in a capture."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events]
    return out


def test_trainer_spans_nest_per_round(tmp_path):
    """run(profile_dir=) records one trainer.round per round, its children
    inside it with the same round id, one trainer.drain after the last, and
    no host span named after a device phase (those are scopes only)."""
    cfg = TrainerConfig(n_clients=N, depositum=_cfg(), log_every=1)
    tr = FederatedTrainer(_toy_model(), cfg, schedule=_sched())
    tr.run(tr.init_state(jax.random.PRNGKey(0)), _trainer_batches(3), 3,
           profile_dir=str(tmp_path))
    events = _host_events(str(tmp_path))
    count = Counter(e[0] for e in events)
    for name in ("trainer.round",) + TRAINER_CHILDREN:
        assert count[name] == 3, (name, count[name])
    assert count["trainer.drain"] == 1
    rounds = {e[3]["round"]: e for e in events if e[0] == "trainer.round"}
    assert sorted(rounds) == [1, 2, 3]
    for name, start, end, args in events:
        if name in TRAINER_CHILDREN:
            _, r_start, r_end, _ = rounds[args["round"]]
            assert r_start <= start <= end <= r_end, (name, args)
    (drain,) = [e for e in events if e[0] == "trainer.drain"]
    assert drain[1] >= rounds[3][2]
    assert not set(count) & set(PHASES)


def test_round_traces_counts_retraces():
    cfg = TrainerConfig(n_clients=N, depositum=_cfg(), log_every=1)
    tr = FederatedTrainer(_toy_model(), cfg, schedule=_sched())
    state, _ = tr.run(tr.init_state(jax.random.PRNGKey(0)),
                      _trainer_batches(3), 3)
    assert tr.round_traces == 1

    def longer():
        while True:
            yield jnp.zeros((T0, N, 2))

    tr.run(state, longer(), 1)
    assert tr.round_traces == 2


# ---------------------------------------------------------------------------
# Slow: shard_map bit-exactness (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_shardmap_metrics_on_is_bitexact():
    from test_distributed import run_py
    out = run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import DepositumConfig, MixPlan, init as dep_init, \\
            local_then_comm_round
        from repro.core.schedule import MixSchedule
        from repro.obs.metrics import MetricSpec, round_values
        from repro.obs.record import Telemetry
        from repro.training.backends import ShardMapBackend

        n, d, T0, rounds = 8, 32, 2, 4
        key = jax.random.PRNGKey(0)
        A = jax.random.normal(key, (n, 16, d)) * 0.3
        b = jax.random.normal(jax.random.fold_in(key, 1), (n, 16))

        def grad_fn(x, batch):
            def one(xi, Ai, bi):
                return 2.0 * Ai.T @ (Ai @ xi - bi) / Ai.shape[0]
            return jax.vmap(one)(x, A, b), {}

        cfg = DepositumConfig(alpha=0.05, comm_period=T0, prox_name="l1",
                              prox_kwargs={"lam": 1e-4})
        sched = MixSchedule.constant(MixPlan.from_topology("ring", n))
        mesh = jax.make_mesh((8,), ("clients",))
        backend = ShardMapBackend(mesh=mesh, n_clients=n)
        mixer = backend.mixer_for(sched)
        batches = jnp.zeros((T0, n, 1))

        round_off = jax.jit(lambda s, bt: local_then_comm_round(
            s, bt, grad_fn, cfg, mixer))
        tel = Telemetry(MetricSpec(buffer=rounds + 1))

        def round_on(s, bt, carry, le):
            # metrics on the global (sharded) state OUTSIDE the shard_map
            # body: jnp client-axis reductions lower to collectives and the
            # recorder stays one host writer
            s, aux = local_then_comm_round(s, bt, grad_fn, cfg, mixer)
            vals = round_values(s, cfg, mixer=sched, aux=aux, n=n)
            r = (s.t - 1) // cfg.comm_period
            return s, tel.record_and_emit(carry, vals, r, le)

        round_on = jax.jit(round_on)
        s_off = s_on = dep_init(jnp.zeros((d,)), n)
        carry = tel.init_carry()
        le = jnp.asarray(1, jnp.int32)
        for _ in range(rounds):
            s_off, _ = round_off(s_off, batches)
            s_on, carry = round_on(s_on, batches, carry, le)
        tel.sync()
        for a, c in zip(jax.tree_util.tree_leaves(s_off),
                        jax.tree_util.tree_leaves(s_on)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        events = tel.events(0)
        assert [e["round"] for e in events] == [1, 2, 3, 4], events
        assert all(np.isfinite(e["consensus_x"]) for e in events)
        assert events[0]["wire_bytes"] == 2 * n * d * 4 * 2  # ring, 2 vars
        print("OK", len(events))
    """))
    assert "OK 4" in out


# ---------------------------------------------------------------------------
# Slow: O(1/T) smoke — running means of the theory streams decrease
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Async runtime: the staleness stream and metrics-off equivalence
# ---------------------------------------------------------------------------

def _async_trainer(telemetry, rounds, seed=3):
    from repro.core.staleness import StragglerModel
    from repro.training.async_runtime import AsyncConfig, AsyncTrainer

    cfg = TrainerConfig(n_clients=N, topology="ring", depositum=_cfg(),
                        log_every=1)
    sm = StragglerModel.exponential(1.0, N, seed=seed).with_faults(
        p_drop=0.2, p_dup=0.2)
    return AsyncTrainer(_toy_model(), cfg, straggler=sm,
                        async_cfg=AsyncConfig(tau=2), telemetry=telemetry)


def _run_async(trainer, rounds):
    from repro.training.async_runtime import tabulate_batches
    return trainer.run(
        trainer.init_state(jax.random.PRNGKey(0)),
        tabulate_batches(_trainer_batches(rounds), rounds), rounds)


def test_async_staleness_stream_matches_replay_recompute():
    """The recorded ``staleness`` stream IS the replay log's recompute:
    per-round mean staleness of applied arrivals, in float32, with empty
    cohorts recording 0.0.  Recorder rounds are 1-based; the replay list
    indexes learner rounds from 0."""
    from repro.core.staleness import replay_cohorts, replay_staleness

    rounds = 8
    tel = Telemetry.memory(MetricSpec(buffer=rounds + 1))
    tr = _async_trainer(tel, rounds)
    _run_async(tr, rounds)
    tel.sync()
    events = tel.events(0)
    assert len(events) == rounds
    rep = replay_staleness(tr.events)
    cohorts = replay_cohorts(tr.events)
    assert any(s > 0 for s in rep), "no stale applies; test is vacuous"
    for e in events:
        k = e["round"] - 1
        assert np.float32(e["staleness"]) == np.float32(rep[k])
        assert e["cohort_size"] == len(cohorts[k])


def test_async_metrics_on_is_bitexact_with_metrics_off():
    """Attaching telemetry must not perturb the async trajectory: same
    straggler seeds, metrics on vs off, bit-identical final states and
    identical replay logs."""
    rounds = 6
    tr_on = _async_trainer(True, rounds)
    tr_off = _async_trainer(None, rounds)
    s_on, _ = _run_async(tr_on, rounds)
    s_off, _ = _run_async(tr_off, rounds)
    assert tr_on.events == tr_off.events
    for a, b in zip(jax.tree_util.tree_leaves(s_on),
                    jax.tree_util.tree_leaves(s_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_streams_decrease_in_running_mean():
    """Theorem 1 bounds (1/T) Σ_t E[...] by O(1/T): on a composite
    quadratic the *running means* of the recorded prox-gradient-mapping
    and consensus-error streams must trend down over T rounds."""
    rounds = 60
    n, d = 6, 24
    key = jax.random.PRNGKey(7)
    A = jax.random.normal(key, (n, 32, d)) * 0.4
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, 32))

    def grad_fn(x, batch):
        def one(xi, Ai, bi):
            return 2.0 * Ai.T @ (Ai @ xi - bi) / Ai.shape[0]
        return jax.vmap(one)(x, A, b), {}

    cfg = _cfg(alpha=0.02)
    sched = MixSchedule.constant(MixPlan.from_topology("ring", n))
    tel = Telemetry(MetricSpec(buffer=rounds + 1))
    # heterogeneous init: consensus error starts genuinely nonzero
    params0 = jax.random.normal(jax.random.fold_in(key, 2), (d,))
    sweep_run(params0, grad_fn, cfg, sched,
              stack_hypers([Hyper.create(alpha=0.02, lam=1e-4)]),
              jnp.zeros((rounds, T0, n, 1)), n_clients=n, telemetry=tel)
    tel.sync()
    for name in ("prox_grad_sq", "consensus_x"):
        vals = np.asarray(tel.stream(name, 0), np.float64)
        assert len(vals) == rounds
        assert np.all(np.isfinite(vals)) and np.all(vals >= 0), name
        running = np.cumsum(vals) / np.arange(1, rounds + 1)
        # the momentum direction ν ramps from zero, so both streams rise
        # before decaying — the O(1/T) trend holds after a T/3 burn-in:
        # from there the running mean is nonincreasing and clearly drops
        q = rounds // 3
        tail = running[q:]
        assert np.all(tail[1:] <= tail[:-1] * 1.001 + 1e-12), (
            name, tail[:: max(1, q // 2)])
        assert running[-1] < 0.8 * running[q], (
            name, running[q], running[-1])
