"""The readers of the program's own spans and scopes: on hand-made program
traces, and on small extracts recorded on a TPU v5e."""
from __future__ import annotations

import gzip
import json
import re

import pytest

from chipbench import BENCH_DIR
from chipbench import program_trace as pt
from chipbench import trace as tr
from chipbench.spec import Cell

TRAIN_METRICS = ("train_forward_ms", "train_backward_ms",
                 "train_optimizer_ms")
SERVE_METRICS = ("serve_admit_idle_ms", "serve_turn_host_ms",
                 "serve_prefills_per_step")
FWD = "jit(step_fn)/jvp(model)/while/body/dot_general"
BWD = "jit(step_fn)/transpose(jvp(model))/while/body/dot_general"
OPT = "jit(step_fn)/optimizer/sqrt"
CAST = "jit(step_fn)/cast_params/convert_element_type"


class _Ctx:
    """What a reader sees, with the program trace already parsed."""

    def __init__(self, cell, program_trace, trace=None):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.trace = trace if trace is not None else {
            "window": (program_trace or {}).get("window", [0, 1]),
            "devices": {}, "host": []}
        self.counters = {}
        self.program_trace = program_trace


def _read(cell_name, metric, program_trace):
    cell = Cell(cell_name)
    return cell.reader(metric).read(_Ctx(cell, program_trace))


def _idle_covered_share(t):
    """Share (%) of the device's idle time in the window that lies inside
    some program span."""
    t0, t1 = t["window"]
    busy = tr.clip(tr.op_intervals(t["devices"]["0"]["ops"]), t0, t1)
    spans = tr.clip([(s[1], s[1] + s[2]) for s in t["spans"]], t0, t1)
    idle = tr.subtract([(t0, t1)], busy)
    return 100.0 * (1.0 - tr.subtract([(t0, t1)], busy + spans) / idle)


def _prefills_inside_admissions(t):
    """Share (%) of the prefill programs that lie wholly inside an
    admission: the host's spans and the device's ops share a clock."""
    admits = [(s[1], s[1] + s[2]) for s in t["spans"]
              if s[0] == "serve.admit"]
    mods = [m for m in t["devices"]["0"]["modules"]
            if m[0].startswith("jit_prefill")]
    inside = [any(a <= m[1] and m[1] + m[2] <= b for a, b in admits)
              for m in mods]
    return 100.0 * sum(inside) / len(inside)


def _train_trace(scoped=True):
    """Two steps of 100 ns: forward 20 (flash_fwd 10 of it), cast 5,
    backward 30 (flash_dq 10), optimizer 25 (lamb_apply 10), unscoped 10;
    a step-program's ``while`` container and an op outside any step.
    Unscoped, the ops keep the name stack autodiff gives them alone."""
    def s(scope):
        return scope if scoped else re.sub(
            r"jvp\(model\)", "jvp()", scope).replace(
            "/optimizer", "").replace("/cast_params", "")

    ops = []
    for t0 in (1000, 2000):
        ops += [["while.3", t0, 100, s("jit(step_fn)/while")],
                ["convert_element_type.1", t0, 5, s(CAST)],
                ["fusion.1", t0 + 5, 10, s(FWD)],
                ["flash_fwd.8", t0 + 15, 10, ""],
                ["fusion.2", t0 + 25, 20, s(BWD)],
                ["flash_dq.13", t0 + 45, 10, ""],
                ["fusion.3", t0 + 55, 15, s(OPT)],
                ["lamb_apply.2", t0 + 70, 10, ""],
                ["copy.4", t0 + 80, 10, ""]]
    ops.append(["fusion.9", 3000, 50, s(FWD)])
    modules = [["jit_step_fn(1)", 1000, 100], ["jit_step_fn(1)", 2000, 100],
               ["jit_other(2)", 3000, 50]]
    return {"window": [900, 3100], "spans": [],
            "devices": {"0": {"ops": ops, "modules": modules}}}


def test_step_parts_sum_to_the_busy_time_per_step():
    parts = pt.step_parts_ms(_train_trace())
    assert parts == pytest.approx({
        "forward": 20e-6, "cast": 5e-6, "backward": 30e-6,
        "optimizer": 25e-6, "busy": 90e-6, "other": 10e-6})


def test_train_readers_on_a_hand_made_trace():
    t = _train_trace()
    got = {m: _read("bert-large.train.seq128", m, t) for m in TRAIN_METRICS}
    assert got == pytest.approx({"train_forward_ms": 25e-6,
                                 "train_backward_ms": 30e-6,
                                 "train_optimizer_ms": 25e-6})


@pytest.mark.parametrize("metric", TRAIN_METRICS + SERVE_METRICS)
def test_readers_give_nothing_without_the_program_s_spans_or_scopes(metric):
    """The parent program: kernels by name, but no scopes and no spans."""
    cell = ("smollm-360m.serve.steady" if metric.startswith("serve")
            else "bert-large.train.seq128")
    bare = _serve_trace(spans=False) if metric.startswith("serve") \
        else _train_trace(scoped=False)
    assert _read(cell, metric, bare) is None
    assert _read(cell, metric, None) is None


def test_load_finds_no_trace_directory_and_caches_that():
    cell = Cell("bert-large.train.seq128")
    ctx = _Ctx(cell, None)
    del ctx.program_trace
    ctx.cell = type("C", (), {"name": "no-such-cell-was-traced"})()
    assert pt.load(ctx) is None and ctx.program_trace is None


# an xplane as the TPU v5e writes one, in the profiler's own text format:
# the name stack is a stat of each op's event metadata (a string, or a
# reference to a stat name), which ``ProfileData`` does not expose
XPLANE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 90000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 40000 }
    events { metadata_id: 3 offset_ps: 70000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()"
    display_name: "fusion.1"
    stats { metadata_id: 11 str_value: "jit(step_fn)/jvp(model)/dot:" }
    stats { metadata_id: 12 str_value: "loop fusion" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion()"
    display_name: "fusion.2" stats { metadata_id: 11 ref_value: 21 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy()"
    display_name: "copy.3" stats { metadata_id: 12 str_value: "copy" } } }
  event_metadata { key: 9 value { id: 9 name: "jit_step_fn(7)" } }
  stat_metadata { key: 11 value { id: 11 name: "tf_op" } }
  stat_metadata { key: 12 value { id: 12 name: "hlo_category" } }
  stat_metadata { key: 21 value { id: 21
    name: "jit(step_fn)/transpose(jvp(model))/dot" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000 duration_ps: 50000
      stats { metadata_id: 31 int64_value: 4 }
      stats { metadata_id: 32 int64_value: 1 } }
    events { metadata_id: 2 offset_ps: 60000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "serve.admit" } }
  event_metadata { key: 2 value { id: 2 name: "bench:admit" } }
  stat_metadata { key: 31 value { id: 31 name: "rid" } }
  stat_metadata { key: 32 value { id: 32 name: "_r" } } }
"""


def test_read_takes_scopes_from_op_metadata_and_args_from_spans(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XPLANE))
    t = pt.read(str(path), [0, 10**6])
    assert t["spans"] == [["serve.admit", 1005, 50, {"rid": 4}]]
    dev = t["devices"]["0"]
    assert dev["ops"] == [
        ["fusion.1", 1000, 30, "jit(step_fn)/jvp(model)/dot", "loop fusion"],
        ["fusion.2", 1030, 40, "jit(step_fn)/transpose(jvp(model))/dot", ""],
        ["copy.3", 1070, 20, "", "copy"]]
    assert dev["modules"] == [["jit_step_fn(7)", 1000, 90]]
    assert pt.step_parts_ms(t) == pytest.approx({
        "forward": 30e-6, "backward": 40e-6, "optimizer": 0.0, "cast": 0.0,
        "busy": 90e-6, "other": 20e-6})


def _serve_trace(spans=True):
    """Decode steps of 100 at 0, 150, 300; between the first two an
    admission [110, 140) holding a prefill [115, 125); the device idle
    [100, 110) (turn), [125, 140) (admission), [140, 150) (turn);
    between the last two only the turn, [250, 300)."""
    ops = [["fusion.1", 0, 100, ""], ["fusion.2", 150, 100, ""],
           ["fusion.3", 300, 100, ""], ["fusion.4", 115, 10, ""]]
    modules = [["jit_step(1)", 0, 100], ["jit_step(1)", 150, 100],
               ["jit_step(1)", 300, 100], ["jit_prefill(2)", 115, 10]]
    program = [["serve.decode", 0, 105, {}], ["serve.admit", 110, 30,
                                             {"rid": 4}],
               ["serve.decode", 145, 108, {}], ["serve.decode", 295, 108, {}]]
    return {"window": [0, 420], "spans": program if spans else [],
            "devices": {"0": {"ops": ops, "modules": modules}}}


def test_serve_readers_on_a_hand_made_trace():
    t = _serve_trace()
    got = {m: _read("smollm-360m.serve.steady", m, t) for m in SERVE_METRICS}
    # admission: 30 long, 10 busy; turns: 10 + 10 and 50, median of [20, 50]
    assert got == pytest.approx({"serve_admit_idle_ms": 20e-6,
                                 "serve_turn_host_ms": 35e-6,
                                 "serve_prefills_per_step": 1 / 3})


def test_idle_covered_by_spans_and_prefills_inside_admissions():
    t = _serve_trace()
    # idle: [100,115) [125,150) [250,300) [400,420) = 15+25+50+20 = 110;
    # uncovered by a span: [105,110) [140,145) [253,295) [403,420) = 69
    assert _idle_covered_share(t) == pytest.approx(100 * (1 - 69 / 110))
    assert _prefills_inside_admissions(t) == 100.0


# two steps of seq 128 (ops' name stacks cut to their first three
# components, which place every op) and three serving turns
RECORDED = {"train": BENCH_DIR / "testdata" / "train128.program.json.gz",
            "serve": BENCH_DIR / "testdata" / "serve.program.json.gz",
            # one step of bert-large.train.seq128.data4 on two of its four
            # chips (PR 16), each op with its HLO category
            "train4": BENCH_DIR / "testdata" / "train128data4.program.json.gz"}


def _recorded(kind):
    with gzip.open(RECORDED[kind], "rt") as f:
        return json.load(f)


def test_recorded_train_extract_splits_the_step():
    t = _recorded("train")
    parts = pt.step_parts_ms(t)
    assert parts is not None
    for p in ("forward", "backward", "optimizer", "cast"):
        assert parts[p] > 0, p
    assert 0 <= parts["other"] < parts["busy"]
    # the parts are disjoint pieces of the step's busy time
    total = sum(parts[p] for p in pt.PARTS) + parts["other"]
    assert total == pytest.approx(parts["busy"], rel=0.01)
    for m in TRAIN_METRICS:
        assert _read("bert-large.train.seq128", m, t) > 0


def test_recorded_serve_extract_shares_the_device_clock():
    t = _recorded("serve")
    assert _prefills_inside_admissions(t) >= 95.0
    assert _idle_covered_share(t) >= 95.0
    for m in SERVE_METRICS:
        assert _read("smollm-360m.serve.steady", m, t) > 0


def test_recorded_four_chip_extract_finds_the_fused_reduce_scatter():
    t = _recorded("train4")
    ops = t["devices"]["0"]["ops"]
    # the embedding's gradient reduce-scatter: a fusion, known by category
    fused = [op for op in ops
             if tr.is_collective(op) and not tr.COLLECTIVE.match(op[0])]
    assert [op[4] for op in fused] == ["all-reduce-scatter fusion"]
    # the weight gathers: rings of collective permutes inside the loop
    assert any(op[0].startswith("collective-permute-done") for op in ops)
    exposed = _read("bert-large.train.seq128.data4",
                    "train_collective_exposed_ms", t)
    by_name = tr.subtract(
        tr.op_intervals(ops, lambda op: bool(tr.COLLECTIVE.match(op[0]))),
        tr.op_intervals(ops, lambda op: not tr.is_collective(op)
                        and not tr.is_container(op))) / 1e6
    assert by_name < exposed < 20.0
    parts = pt.step_parts_ms(t)
    assert exposed < parts["busy"]
    assert parts["forward"] + parts["backward"] + parts["optimizer"] \
        + parts["cast"] <= parts["busy"] + 1e-6
