"""What the training and serving runners share: the program's model
configuration built from a configuration file, keys from the seed, the
device description, traced sections, per-layer metric reading, checks
against limits and the result line."""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import trace as tr
from chipbench.traffic import seed_words


def program_config(config: dict, traffic: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    published keys mapped to its fields, then every key of the file's
    ``program`` block under its own field name.  A ``program`` key that is
    no field of ``ModelConfig`` is an error that names it.  Widths given
    in both places are held together by ``check_layout``, which compares
    the reference's weight layout, built from the published keys, with the
    program's."""
    from repro.configs.base import ModelConfig

    program = config["program"]
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(program) - fields)
    if unknown:
        raise KeyError(f"{config['name']}: program key(s) {unknown} are not "
                       "fields of the program's ModelConfig")
    heads = config["num_attention_heads"]
    published = dict(
        name=config["name"], n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=heads,
        n_kv_heads=config.get("num_key_value_heads", heads),
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=config["rope_theta"],
        mlm_max_predictions=traffic.get("max_predictions"))
    if "head_dim" in config:
        published["head_dim"] = config["head_dim"]
    return ModelConfig(**{**published, **program})


def key_data(seed: int) -> jnp.ndarray:
    return jnp.asarray(seed_words(seed), jnp.uint32)


def check_layout(ours, theirs) -> None:
    """The benchmark's weight layout must be the program's, leaf for leaf."""
    a = jax.tree_util.tree_flatten_with_path(ours)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    sa = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in a}
    sb = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in b}
    if sa != sb:
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"{sorted(set(sa.items()) ^ set(sb.items()))[:6]}")


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations (cache hits excluded) as they happen."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class GcWatch:
    """Freezes what set-up allocated (the program, its traces and the
    harness) out of the collector, so that a full collection in the window
    does not walk it, and times the collections that still run."""

    def __init__(self):
        gc.collect()
        gc.freeze()
        self.longest, self.n, self._t0 = 0.0, 0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif info.get("generation") == 2:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t0)

    def close(self) -> str:
        gc.callbacks.remove(self._on)
        gc.unfreeze()
        return (f"{self.n} full collections since set-up, the longest "
                f"{self.longest:.3f} s")


class Traced:
    """A profiled section with its own host span; ``.trace`` holds the
    extracted events once the section has ended."""

    def __init__(self, outdir: Path):
        self.outdir = outdir
        self.trace: Optional[dict] = None
        self.xplane: Optional[str] = None

    def start(self, span: bool = True) -> None:
        """Start the profiler, and the traced span with it unless the
        caller opens it later (``enter``)."""
        shutil.rmtree(self.outdir, ignore_errors=True)
        jax.profiler.start_trace(str(self.outdir))
        self._span = None
        if span:
            self.enter()

    def enter(self) -> None:
        self._span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self._span.__enter__()

    def exit(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self) -> None:
        self.exit()
        jax.profiler.stop_trace()
        self.xplane = tr.latest_xplane(str(self.outdir))
        self.trace = tr.extract(self.xplane)

    def cleanup(self) -> None:
        shutil.rmtree(self.outdir, ignore_errors=True)


class Ctx:
    """What a per-layer metric reader sees."""

    def __init__(self, cell, trace: Optional[dict], counters: dict,
                 device_kind: str):
        from chipbench.spec import peak

        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.trace = trace
        self.counters = counters
        self.peak = peak(cell.peaks, device_kind)


def read_per_layer(cell, ctx: Ctx) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.metrics("per_layer"):
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def seam(obj, name: str):
    """A private attribute of the program that the benchmark drives (PERF.md
    lists each): one that is renamed or gone stops the run here, by name,
    instead of changing what the window measures."""
    if not hasattr(obj, name):
        raise RuntimeError(f"{type(obj).__name__}.{name}, which the benchmark "
                           "drives, is gone from the program")
    return getattr(obj, name)


class HostWatch:
    """Samples this process's CPU time on a thread of its own every
    ``every`` seconds, so that a pause in the window can be told apart:
    the process busy on the host, the process waiting (little CPU, the
    sampler ticking on), or the whole process held (the sampler's own
    ticks stop then too)."""

    def __init__(self, every: float = 0.1):
        self.every = every
        self.samples = []
        self._stop = threading.Event()
        self._sample()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _sample(self) -> None:
        self.samples.append((now(), time.process_time()))

    def _run(self) -> None:
        while not self._stop.wait(self.every):
            self._sample()

    def close(self) -> None:
        self._stop.set()
        self._thread.join()
        self._sample()

    def across(self, t0: float, t1: float) -> str:
        """What the process did between t0 and t1."""
        s = np.asarray(self.samples, np.float64)
        a = max(int(np.searchsorted(s[:, 0], t0, side="right")) - 1, 0)
        b = min(int(np.searchsorted(s[:, 0], t1)), len(s) - 1)
        ticks = np.diff(s[a:b + 1, 0])
        return (f"{s[b, 0] - s[a, 0]:.3f} s sampled: process CPU "
                f"{s[b, 1] - s[a, 1]:.3f} s, longest sampler tick "
                f"{(ticks.max() if len(ticks) else 0.0):.3f} s")


def longest_pause(stamps, t0: float, watch: Optional[HostWatch] = None,
                  detail: Optional[List[str]] = None) -> str:
    """The longest interval between consecutive completions, against the
    median one, and what the host did in it and over the whole window
    (``detail[j]``: how the run spent the interval ending at ``stamps[j]``):
    a stall of the host or the device shows here."""
    if len(stamps) < 3:
        return "too few completions to time"
    stamps = np.asarray(stamps, np.float64)
    gaps = np.diff(stamps)
    i = int(np.argmax(gaps))
    out = (f"longest pause between completions {gaps[i]:.3f} s at "
           f"{stamps[i] - t0:.3f} s into the window (median "
           f"{float(np.median(gaps)):.3f} s)")
    if detail is not None:
        out += f" ({detail[i + 1]})"
    if watch is not None:
        out += (f"; in it: {watch.across(stamps[i], stamps[i + 1])}; over "
                f"the window: {watch.across(t0, stamps[-1])}")
    return out


def quantile(values, q: float) -> float:
    """numpy's linear-interpolation percentile, q in [0, 100]."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """(correct, checks): every compared number against its limit.  A
    number that is missing or not finite fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = readings.get(name, math.nan)
        good = math.isfinite(v) and v <= limit
        ok &= good
        checks[name] = {"value": v if math.isfinite(v) else None,
                        "limit": limit}
    return ok, checks


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: dict, breakdown: Optional[dict] = None) -> None:
    """The run's result: the checks as the last lines on stderr, and one
    JSON object as the last line on stdout with the checks last."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(out), flush=True)


def now() -> float:
    return time.perf_counter()
