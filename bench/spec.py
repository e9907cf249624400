"""Load a cell of BENCHMARK.json with its configuration, traffic, limits
and per-layer metric readers, all found by name under ``bench/``."""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/limits/<cell>.json
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def model(self) -> dict:
        """The configuration's sizes plus the keys the reference reads."""
        return {**self.config["model"],
                "vocab_pad_multiple": self.config["vocab_pad_multiple"]}

    def weights(self):
        """The one jitted ``key -> params`` both sides of a run use."""
        from bench.reference import init
        from bench.reference.depositum import family

        import jax.numpy as jnp

        m = self.model
        return init.make_init(family(m), m, jnp.dtype(m["dtype"]))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    d = root / "bench"
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(d / "configs" / f"{w['config']}.json"),
        traffic=load_json(d / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(d / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def pair_cell(config: str, traffic: str, chips: int,
              root: Path = ROOT) -> Cell:
    """A configuration under a traffic mix that ``BENCHMARK.json`` does not
    hold as a cell (for calibration and for witnesses); no limits."""
    d = root / "bench"
    return Cell(name=f"{config}.{traffic}", chips=chips,
                config=load_json(d / "configs" / f"{config}.json"),
                traffic=load_json(d / "traffic" / f"{traffic}.json"),
                limits={}, end_to_end=[], per_layer=[])


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of bench/metrics/<name>.py."""
    return importlib.import_module(f"bench.metrics.{name}").read


def flops_module(family: str):
    return importlib.import_module(f"bench.flops.{family}")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table['devices'])}")
    return table["devices"][device_kind]
