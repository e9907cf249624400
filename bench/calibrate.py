#!/usr/bin/env python3
"""Readings that the cell's limits are set from, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3] [--out FILE]

For every seed of ``--seeds`` it builds the program's state from the seed,
drives the checked rounds through ``FederatedTrainer.run`` and compares
them with the float32 reference, as ``run.py`` does (no window).  For
every seed of ``--control-seeds`` it compares the control (the reference
with float8 e4m3 operands and state) with the reference, and the same
arithmetic with the state held in float32, and for every
seed of ``--fault-seeds`` the reference with each fault planted (half of
the batch left out, where the batch has two rows or more; the exchange
between clients left out).  Prints one
JSON line per reading and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check, traffic as traffic_mod  # noqa: E402
from bench.spec import load_cell, pair_cell  # noqa: E402

FAULTS = {"half_batch": {"half_batch": True}, "no_mix": {"no_mix": True}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--pair", nargs=3, metavar=("CONFIG", "TRAFFIC", "CHIPS"),
                    help="a configuration and traffic mix that is not a cell")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = (load_cell(args.workload) if args.workload else
            pair_cell(args.pair[0], args.pair[1], int(args.pair[2])))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    from bench.program import Program, batch_feed
    from bench.reference.depositum import ReferenceRun
    from bench.run import checked_rounds, compile_cache

    compile_cache()
    tr = cell.traffic
    vocab, n = cell.model["vocab_size"], tr["n_clients"]
    rows = lambda seed: traffic_mod.make_rounds(
        tr, vocab, seed, 0, tr["check_rounds"])
    out = []

    def emit(rec):
        out.append(rec)
        print(json.dumps(rec), flush=True)

    refs = {}

    def reference(kind="reference", **fault):
        key = (kind, tuple(sorted(fault)))
        if key not in refs:
            refs[key] = ReferenceRun(cell.model, tr, devices, init,
                                     numerics=kind,
                                     **fault)
        return refs[key]

    init = cell.weights()
    prog = Program(cell, devices, init) if args.seeds else None
    for seed in args.seeds:
        t = time.perf_counter()
        state = prog.init_state(seed)
        feed = batch_feed(rows(seed), jax.profiler.TraceAnnotation)
        state, readings = checked_rounds(prog, state, feed, seed)
        del state
        gc.collect()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = reference().run(seed, rows(seed))
        emit({"kind": "program", "seed": seed, "prog_s": t_prog,
              "ref_s": time.perf_counter() - t,
              "prog_loss": readings["loss"], "ref_loss": ref["loss"],
              **check.compare(readings, ref),
              "grad_leaves": check.leaf_gaps(readings, ref, "grad_norm"),
              "change_leaves": check.leaf_gaps(readings, ref, "change_norm"),
              "change_means": {n: [float(p.mean()), float(r.mean())]
                               for n, p, r in zip(ref["names"],
                                                  readings["change_norm"],
                                                  ref["change_norm"])}})
    for seed in sorted(set(args.control_seeds + args.fault_seeds)):
        base = reference().run(seed, rows(seed))
        kinds = ([("control", {}), ("float32", {})]
                 if seed in args.control_seeds else []) + \
            ([(k, f) for k, f in FAULTS.items()
              if k != "half_batch" or tr["batch"] > 1]
             if seed in args.fault_seeds else [])
        for kind, fault in kinds:
            t = time.perf_counter()
            other = reference(kind if kind in ("control", "float32") else
                              "reference", **fault).run(seed, rows(seed))
            emit({"kind": kind, "seed": seed,
                  "ref_s": time.perf_counter() - t,
                  "loss": other["loss"], "ref_loss": base["loss"],
                  **check.compare(other, base),
                  "change_leaves": check.leaf_gaps(other, base,
                                                   "change_norm")})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
