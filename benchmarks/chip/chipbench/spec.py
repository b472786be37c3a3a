"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, the
configuration file and its plain reference, the traffic mix, the limits
of its ``correct`` check, the per-layer metric readers and the peaks."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from chipbench import BENCH_DIR, REPO


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark(repo: Path = REPO) -> dict:
    return load_json(repo / "BENCHMARK.json")


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything a run of one workload needs, read from files."""

    def __init__(self, name: str, bench: Optional[dict] = None,
                 repo: Path = REPO, bench_dir: Path = BENCH_DIR):
        self.bench = bench if bench is not None else benchmark(repo)
        self.entry = _named(self.bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = _named(self.bench["configs"], self.entry["config"],
                           "configuration")
        cfg_path = repo / cfg_entry["file"]
        self.config = load_json(cfg_path)
        self.reference_path = cfg_path.with_name(cfg_path.stem + ".ref.py")
        self.reference = load_module(
            self.reference_path,
            "ref_" + cfg_path.stem.replace("-", "_").replace(".", "_"))
        self.traffic = load_json(bench_dir / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.limits = load_json(bench_dir / "limits" / f"{name}.json")
        self.peaks = load_json(bench_dir / "peaks.json")
        self.bench_dir = bench_dir
        self.check_counts()

    def metrics(self, section: str) -> List[dict]:
        """The metrics of ``section`` (end_to_end | per_layer) this cell
        reports: those without a ``workloads`` key, and those naming it."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]

    def check_counts(self) -> None:
        """Each per-layer metric's reader names, in ``NEEDS``, the counts
        it takes from the configuration's reference; one that is missing
        stops the run at set-up, by name."""
        for m in self.metrics("per_layer"):
            for fn in getattr(self.reader(m["name"]), "NEEDS", ()):
                if not callable(getattr(self.reference, fn, None)):
                    raise AttributeError(
                        f"{self.name} reports {m['name']}, which needs "
                        f"{fn}() from {self.reference_path.name}; it has "
                        "none")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           "metric_" + metric.replace(".", "_"))


def peak(peaks: Dict[str, dict], device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(peaks)})")
    return peaks[device_kind]
