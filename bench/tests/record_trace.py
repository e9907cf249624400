#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reads, on a TPU.

    python bench/tests/record_trace.py <out_dir>

Runs the tiny Mamba-2 cell of ``tiny.py`` (4 clients, fused
kernels) for a few rounds under the profiler, with the benchmark's host
spans, and writes ``tiny.xplane.pb.gz`` and the compiled round program's
HLO text ``tiny.hlo.txt.gz`` to ``out_dir``.
"""
from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

ROUNDS = 3


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench import traffic as traffic_mod
    from bench.program import Program, batch_feed, import_program
    from bench.tests.tiny import tiny_cell
    from bench.trace import Capture

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    import_program()
    cell = tiny_cell()
    tr = cell.traffic
    prog = Program(cell, jax.devices()[:1], cell.weights())
    state = prog.init_state(7)
    rounds = traffic_mod.make_rounds(tr, cell.model["vocab_size"], 7, 0,
                                     2 + ROUNDS)
    shapes = {k: jax.ShapeDtypeStruct(rounds.shape[1:-1] + (tr["seq_len"],),
                                      jnp.int32) for k in ("tokens", "labels")}
    hlo = prog.trainer.lower_round(state, shapes).compile().as_text()
    feed = batch_feed(rounds, jax.profiler.TraceAnnotation)
    state, _ = prog.trainer.run(state, feed, 2)
    cap = Capture().__enter__()
    with jax.profiler.TraceAnnotation("bench.window"):
        state, _ = prog.trainer.run(state, feed, ROUNDS)
    cap.__exit__(None, None, None)
    os.makedirs(out, exist_ok=True)
    (src,) = glob.glob(os.path.join(cap.dir, "**", "*.xplane.pb"),
                       recursive=True)
    with open(src, "rb") as f, gzip.open(os.path.join(
            out, "tiny.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with gzip.open(os.path.join(out, "tiny.hlo.txt.gz"), "wt") as g:
        g.write(hlo)
    shutil.rmtree(cap.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
