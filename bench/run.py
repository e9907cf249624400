#!/usr/bin/env python3
"""Run one benchmark cell of BENCHMARK.json on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's FederatedTrainer (``fused="require"``) and its
state from ``--seed``, drives it through the first rounds that the check
compares (these compile the round program), times a few more rounds, and
sizes the window from them.  The window runs ``FederatedTrainer.run`` over
that many rounds, fed host token blocks made in set-up, and ends when the
last round's state is ready.  Then the plain float32 reference re-runs the
first rounds from the same seed, and ``correct`` says whether the program
stayed within the cell's limits of it.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
a shorter window and reports its per-layer metrics.  The last line of
stdout is one JSON object; the numbers compared, each with its limit, are
the last lines of stderr and the last key of that object.  Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import itertools
import json
import math
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check, traffic as traffic_mod  # noqa: E402
from bench.spec import Cell, load_cell, metric_reader, peaks  # noqa: E402

#: rounds timed after the checked ones, to size the window
WARM_ROUNDS = 3
#: longest traced window (s), so the trace stays small
TRACE_SECONDS = 3.0
MIN_TRACE_ROUNDS = 4
#: the compile cache, relative to the checkout (gitignored)
CACHE_DIR = ".jax_cache"


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.2f} s] {msg}", file=sys.stderr,
          flush=True)


def compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory of the
    checkout, handed to the program's own switch."""
    import jax

    from bench.program import import_program
    from bench.spec import ROOT

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / CACHE_DIR)
    import_program()
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


def compile_counter():
    import jax

    count = [0]

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def checked_rounds(prog, state, feed, seed: int):
    """The rounds the check compares, through the window's own call and
    feed; returns the state and the program's readings: each round's loss,
    the first gradient's norms and the weights' change, per client and
    leaf."""
    tr = prog.cell.traffic
    readings = {"loss": []}
    for r in range(tr["check_rounds"]):
        state, hist = prog.trainer.run(state, feed, 1)
        readings["loss"].append(hist[-1]["loss"])
        if r == 0:
            # after one round nu = (1 - gamma) beta g1: the first gradient
            scale = 1.0 / ((1.0 - tr["gamma"]) * tr["beta"])
            readings["grad_norm"] = [v * scale
                                     for v in prog.leaf_norms(state.nu)]
    readings["change_norm"] = prog.change_norms(state.x, seed)
    return state, readings


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line's dict."""
    import jax
    import numpy as np

    from bench.program import Program, batch_feed, import_program
    from bench.reference.depositum import ReferenceRun
    from bench.trace import Capture, scope_map

    import_program()
    compiles = compile_counter()
    tr = cell.traffic
    n, T0 = tr["n_clients"], tr["comm_period"]
    vocab = cell.model["vocab_size"]
    n_check = tr["check_rounds"]

    init = cell.weights()
    prog = Program(cell, devices, init)
    trainer = prog.trainer
    log(f"cell {cell.name}: {cell.config['name']}, {n} clients on a "
        f"{tr['topology']} ({trainer.backend.name} backend, "
        f"{len(devices)} chip(s)), T0 {T0}, per-client batch "
        f"{tr['batch']} x {tr['seq_len']}, fused="
        f"{trainer.cfg.depositum.fused_mode()}")
    perms = traffic_mod.client_permutations(vocab, n, seed)
    head = traffic_mod.make_rounds(tr, vocab, seed, 0, n_check + WARM_ROUNDS,
                                   perms)
    annotate = jax.profiler.TraceAnnotation
    state = prog.init_state(seed)
    log("state made")
    feed = batch_feed(head, annotate)
    first = next(feed)
    lowered = trainer.lower_round(state, first)
    has_kernel = "tpu_custom_call" in lowered.as_text()
    log(f"round program contains tpu_custom_call: {has_kernel}")
    scopes = {}
    if trace:
        scopes = scope_map(lowered.compile().as_text())
    del lowered

    state, readings = checked_rounds(prog, state,
                                     itertools.chain([first], feed), seed)
    t = time.perf_counter()
    state, _ = trainer.run(state, feed, WARM_ROUNDS)
    t_round = (time.perf_counter() - t) / WARM_ROUNDS
    n_window = max(1, round(seconds / t_round))
    if trace:
        n_window = max(MIN_TRACE_ROUNDS,
                       min(n_window, math.ceil(TRACE_SECONDS / t_round)))
    log(f"checked rounds' losses {readings['loss']}; {t_round:.4f} s per "
        f"warm round -> {n_window} window rounds")
    window = traffic_mod.make_rounds(tr, vocab, seed, n_check + WARM_ROUNDS,
                                     n_window, perms)
    feed = batch_feed(window, annotate)
    setup_s = time.perf_counter() - t_start
    c0 = compiles[0]
    with Capture() if trace else contextlib.nullcontext() as capture:
        t0 = time.perf_counter()
        with annotate("bench.window"):
            state, hist = trainer.run(state, feed, n_window)
        t1 = time.perf_counter()
    in_window = compiles[0] - c0
    log(f"compilations in window: {in_window}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    failed = sum(1 for h in hist if not math.isfinite(h["loss"]))
    losses = [round(h["loss"], 4) for h in hist]
    log(f"window: {n_window} rounds in {t1 - t0:.4f} s; logged losses "
        f"{losses}")
    del state, hist, feed
    gc.collect()

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": n_window, "failed": failed}
    metrics = {}
    if trace:
        from bench.context import Context, busy_seconds, idle_gaps_by_host_span

        tr_data = capture.reduce(len(devices), scopes)
        one_client = jax.eval_shape(init, jax.random.key(0))
        ctx = Context(
            trace=tr_data, rounds=n_window, comm_period=T0,
            tokens=n_window * traffic_mod.round_tokens(tr), chips=len(devices),
            peaks=peaks(dev.device_kind), model=cell.model,
            seq_len=tr["seq_len"],
            client_leaf_bytes=[l.size * l.dtype.itemsize for l in
                               jax.tree_util.tree_leaves(one_client)],
            clients_per_device=n // len(devices))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = float(np.mean(busy_seconds(ctx)))
        device["window_s"] = ctx.window_s
        breakdown = {"device_ops": [list(x) for x in ctx.device_ops()],
                     "idle_gaps": [list(x) for x in idle_gaps_by_host_span(ctx)]}
    else:
        values = {"setup_s": setup_s,
                  "tokens_per_s": n_window * traffic_mod.round_tokens(tr)
                  / (t1 - t0),
                  "peak_hbm_gib": peak / 2 ** 30}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    t = time.perf_counter()
    ref = ReferenceRun(cell.model, tr, devices, init).run(seed,
                                                          head[:n_check])
    numbers = check.compare(readings, ref)
    correct, checks = check.verdict(numbers, cell.limits)
    log(f"reference: {time.perf_counter() - t:.1f} s; losses {ref['loss']}; "
        f"worst grad leaf {numbers['worst_grad_leaf']}, worst change leaf "
        f"{numbers['worst_change_leaf']}; not compared: " + ", ".join(
            f"{k} {numbers[k]:.6g}" for k in check.NUMBERS
            if k not in checks))
    result.update(correct=correct and failed == 0, metrics=metrics,
                  device=device)
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"{name} {c['value']:.6g} limit {c['limit']:.6g}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    log(f"compile cache: {compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
