"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix, limit and metric reader loads, and the file keeps to the
shape the harness reads."""
import json
import re

import pytest

from bench import check
from bench.spec import BENCH_DIR, ROOT, load_cell, metric_reader, peaks

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_references():
    configs = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert configs == used
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for entry in BENCH["configs"] + BENCH["workloads"] + \
            BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= \
        max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(cell):
    c = load_cell(cell)
    assert c.limits and set(c.limits) <= set(check.NUMBERS)
    assert all(v > 0 for v in c.limits.values())
    assert c.traffic["n_clients"] % c.chips == 0
    assert c.traffic["seq_len"] % c.model["ssm_chunk"] == 0
    assert [m["name"] for m in c.end_to_end] == \
        [m["name"] for m in BENCH["end_to_end"]]
    for m in c.per_layer:
        assert callable(metric_reader(m["name"]))


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]


def test_unknown_device_kind_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")


def test_every_metric_has_a_reader_file():
    for m in BENCH["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
