#!/usr/bin/env python3
"""Compile each cell's round program for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--workload <cell> ...]

Builds the cell's trainer on the devices of a described ``v5e:2x2``
topology (one of them for a one-chip cell, all four on a mesh for a
four-chip cell), lowers the round program on the state's and batches'
shapes with the Pallas kernels compiled by Mosaic, compiles it with the
TPU compiler, and prints ``memory_analysis()`` per device and whether the
program holds the kernels (``tpu_custom_call``) and which collectives.
Nothing runs: the numbers are the compiler's, not a chip's.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.spec import load_cell, load_json, ROOT  # noqa: E402

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
               "reduce-scatter")


def rehearse(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.program import Program, import_program

    import_program()
    import repro.kernels

    cell = load_cell(name)
    devices = list(topo.devices[:cell.chips])
    tr = cell.traffic
    init = cell.weights()
    prog = Program(cell, devices, init)
    from repro.core import init as dep_init

    n = tr["n_clients"]
    shapes = jax.eval_shape(lambda k: dep_init(init(k), n),
                            jax.random.key(0))
    if prog.trainer.backend.name == "shard_map":
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=prog._sharding(s)),
            shapes)
    else:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            shapes)
    batch = {k: jax.ShapeDtypeStruct((tr["comm_period"], n, tr["batch"],
                                      tr["seq_len"]), jnp.int32)
             for k in ("tokens", "labels")}
    real = repro.kernels.interpret_mode
    repro.kernels.interpret_mode = lambda: False
    try:
        t = time.perf_counter()
        compiled = prog.trainer.lower_round(state, batch).compile()
        secs = time.perf_counter() - t
    finally:
        repro.kernels.interpret_mode = real
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    found = {c: len(re.findall(rf"\b{c}(?:-start)?\(", text))
             for c in COLLECTIVES}
    return {"cell": name, "backend": prog.trainer.backend.name,
            "compile_s": round(secs, 1),
            "tpu_custom_call": text.count("tpu_custom_call"),
            "collectives": {k: v for k, v in found.items() if v},
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="*", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in
                              load_json(ROOT / "BENCHMARK.json")["workloads"]]
    for name in names:
        r = rehearse(name, topo)
        print(" ".join(f"{k}={v}" for k, v in r.items()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
