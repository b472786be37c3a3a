"""The trace reduction: idle share, kernel time and exposed collectives,
on hand-made traces and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import gzip
import json

import numpy as np
import pytest

from chipbench import BENCH_DIR
from chipbench import trace as tr

RECORDED = sorted((BENCH_DIR / "testdata").glob("*.trace.json.gz"))


def _trace(ops_by_dev, window=(0, 100), host=()):
    return {"window": list(window),
            "devices": {d: {"ops": [list(o) for o in ops], "modules": []}
                        for d, ops in ops_by_dev.items()},
            "host": [list(h) for h in host]}


def test_idle_share_counts_overlaps_once_and_clips_to_the_window():
    t = _trace({"0": [("fusion.1", -10, 30, ""), ("fusion.2", 10, 20, ""),
                      ("copy.3", 50, 10, ""), ("fusion.4", 95, 20, "")]})
    # busy: [0, 30) + [50, 60) + [95, 100) = 45 of 100
    assert tr.idle_share(t) == pytest.approx(55.0)
    assert tr.busy_s(t) == pytest.approx(45e-9)


def test_idle_share_averages_the_chips():
    t = _trace({"0": [("a", 0, 100, "")], "1": [("a", 0, 50, "")]})
    assert tr.idle_share(t) == pytest.approx(25.0)


def test_exposed_collectives_subtract_overlapping_compute():
    t = _trace({"0": [("all-gather.1", 0, 40, ""), ("fusion.2", 10, 10, ""),
                      ("reduce-scatter.3", 60, 20, ""),
                      ("fusion.4", 70, 30, "")]})
    # all-gather exposed [0,10)+[20,40) = 30; reduce-scatter [60,70) = 10
    assert tr.exposed_collective_ns(t, "0") == pytest.approx(40.0)


def test_kernel_events_by_name_stack():
    t = _trace({"0": [
        ("custom-call.7", 0, 5, "jit(step_fn)/while/body/flash_fwd/pallas_call"),
        ("fusion.1", 5, 5, "jit(step_fn)/while/body/dot_general"),
        ("lamb_apply", 20, 5, "")]})
    assert [e[0] for e in tr.kernel_events(t, "flash_fwd")] == ["custom-call.7"]
    assert [e[0] for e in tr.kernel_events(t, "lamb_apply")] == ["lamb_apply"]
    assert tr.kernel_events(t, "flash_dq") == []


def test_idle_gaps_are_named_by_the_innermost_open_span():
    t = _trace({"0": [("a", 0, 10, ""), ("b", 40, 10, ""), ("c", 55, 45, "")]},
               host=[("bench:train_step", 5, 60), ("bench:input", 20, 20)])
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["input", pytest.approx(30e-9)]
    assert gaps[1] == ["train_step", pytest.approx(5e-9)]


def _sampled_idle(trace, dev, n=20000):
    """Idle share by sampling instants: a second way to the same number."""
    t0, t1 = trace["window"]
    ts = np.linspace(t0, t1, n, endpoint=False)
    busy = np.zeros(n, bool)
    for _, s, d, _ in trace["devices"][dev]["ops"]:
        busy |= (ts >= s) & (ts < s + d)
    return 100.0 * (1.0 - busy.mean())


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_trace_reduces_consistently(path):
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    for dev in t["devices"]:
        one = dict(t, devices={dev: t["devices"][dev]})
        assert tr.idle_share(one) == pytest.approx(_sampled_idle(t, dev),
                                                   abs=0.1)
    assert 0.0 < tr.busy_s(t) <= tr.window_s(t)
    for name, secs in tr.top_ops(t):
        assert secs > 0


def _recorded(name):
    with gzip.open(BENCH_DIR / "testdata" / name, "rt") as f:
        return json.load(f)


class _Ctx:
    def __init__(self, cell, trace, counters):
        import json as _json

        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.trace, self.counters = trace, counters
        self.peak = _json.loads((BENCH_DIR / "peaks.json").read_text())[
            "TPU v5 lite"]


def test_recorded_train_trace_gives_kernel_shares_under_100():
    from chipbench.spec import Cell

    cell = Cell("bert-large.train.seq128")
    t = _recorded("train128.trace.json.gz")
    fwd = tr.kernel_events(t, "flash_fwd")
    assert fwd and all(e[0].startswith("flash_fwd") for e in fwd)
    # one call per layer and step
    steps = len(tr.module_events(t, r"^jit_step_fn"))
    assert len(tr.kernel_events(t, "lamb_apply")) <= 13 * (steps + 1)
    counters = {"chips": 1, "batch_per_chip": 32, "heads": 16, "seq_len": 128,
                "head_dim": 64, "steps_traced": 0, "n_params": 0}
    flash = cell.reader("flash_roofline").read(_Ctx(cell, t, counters))
    assert 0.0 < flash <= 100.0
    idle = cell.reader("train_idle_share").read(_Ctx(cell, t, counters))
    assert 0.0 <= idle < 100.0


def test_recorded_serve_trace_gives_step_times():
    from chipbench.spec import Cell

    cell = Cell("smollm-360m.serve.steady")
    t = _recorded("serve.trace.json.gz")
    ctx = _Ctx(cell, t, {})
    decode = cell.reader("serve_decode_ms").read(ctx)
    assert 50.0 < decode < 500.0
    names = {m[0].split("(")[0] for d in t["devices"].values()
             for m in d["modules"]}
    assert "jit_step" in names


def _with_steps(t, n_steps, step_ns=10_000_000):
    for d in t["devices"].values():
        d["modules"] = [["jit_step_fn", i * step_ns, step_ns]
                        for i in range(n_steps)]
    return t


def test_collective_exposed_ms_reads_only_what_no_op_hides():
    from chipbench.spec import Cell

    cell = Cell("bert-large.train.seq128.data4")
    reader = cell.reader("train_collective_exposed_ms")

    def read(ops_by_dev):
        ctx = _Ctx(cell, _trace({}), {})
        ctx.program_trace = _with_steps(_trace(ops_by_dev, (0, 20_000_000)),
                                        2)
        return reader.read(ctx)

    # a collective wholly under a fusion reads 0
    assert read({"0": [("all-gather-start.1", 0, 4e6, "", "all-gather"),
                       ("fusion.2", 0, 4e6, "", "loop fusion")]}) == 0.0
    # one half hidden reads half of it: 2 ms over 2 steps is 1 ms a step;
    # a reduce-scatter the compiler fused is known by its category
    assert read({"0": [("fusion.3", 0, 4e6, "", "all-reduce-scatter fusion"),
                       ("fusion.4", 2e6, 2e6, "", "loop fusion")]}) == \
        pytest.approx(1.0)
    # the async parts count, a loop's container is no compute; mean of chips
    assert read({
        "0": [("while.1", 0, 2e7, "", "while"),
              ("collective-permute-done.5", 0, 4e6, "",
               "collective-permute-done")],
        "1": [("collective-permute-start.2", 0, 4e6, "",
               "collective-permute-start"),
              ("fusion.6", 0, 4e6, "", "convolution fusion")]}) == \
        pytest.approx(1.0)
    # no collective at all (one chip), or no trace: the metric is silent
    assert read({"0": [("fusion.7", 0, 4e6, "", "loop fusion")]}) is None
    ctx = _Ctx(cell, _trace({}), {})
    ctx.program_trace = None
    assert reader.read(ctx) is None
