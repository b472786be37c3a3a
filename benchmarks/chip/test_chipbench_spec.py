"""BENCHMARK.json keeps to the benchmark's contract, and a cell, a
configuration and a per-layer metric are found by name from new files
alone."""
from __future__ import annotations

import hashlib
import json
import re

import pytest

from chipbench import BENCH_DIR, REPO, tiny
from chipbench.spec import Cell

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_have_just_the_contract_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert c["file"].startswith("benchmarks/chip/")
        assert (REPO / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [x["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[s]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_four_chip_cells_are_at_most_half():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(workload):
    cell = Cell(workload)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = cell.metrics("per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], workload)
        assert callable(cell.reader(m["name"]).read)
    assert cell.limits and hasattr(cell.reference, "param_specs")


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for layer in layers:
        assert "\n" not in layer and 1 <= len(layer) <= 200


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_config_and_metric_need_only_new_files(tmp_path):
    bench = tiny.build(tmp_path)
    copied = {k: v for k, v in _digest(BENCH_DIR).items()
              if not k.startswith(("test_", "testdata"))}
    (bench / "metrics" / "tiny_probe.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    top = json.loads((tmp_path / "BENCHMARK.json").read_text())
    top["per_layer"].append({
        "name": "tiny_probe", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_tokens_per_s", "workloads": [tiny.TRAIN]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(top))
    after = _digest(bench)
    assert all(after[k] == v for k, v in copied.items()
               if k != "peaks.json"), "an existing file was edited"
    cell = Cell(tiny.TRAIN, repo=tmp_path, bench_dir=bench)
    assert cell.config["name"] == "tiny-bert"
    assert cell.traffic["seq_len"] == 16
    assert "tiny_probe" in {m["name"] for m in cell.metrics("per_layer")}
    assert cell.reader("tiny_probe").read(None) == 42.0
    assert Cell(tiny.SERVE, repo=tmp_path, bench_dir=bench).config[
        "name"] == "tiny-lm"
