"""Profiler spans and named scopes inside the program, read back from one
real CPU profiler trace (one short session for the whole file, taken after
every program it runs has compiled)."""
import glob
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.configs.base import TrainConfig
from repro.data import DataPipeline
from repro.data.synthetic import make_batch
from repro.models import build_model
from repro.serve import ContinuousEngine, ServeRequest
from repro.telemetry import EventLog, SpanRecorder, compile_count, trace_span
from repro.train import Trainer
from repro.train.step import make_train_step


def _host_events(xplane):
    """[(name, start_ns, end_ns, stats)] of every host event."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Run a span with args, a tiny engine, a tiny fit and a recorder span
    under one profiler session; returns the host events and run records."""
    model = build_model(tiny_dense())
    params = model.init(jax.random.key(0))
    engine = ContinuousEngine(model, params, n_slots=2, max_len=32)
    prompts = [np.arange(1, 7, dtype=np.int32), np.arange(3, 9, dtype=np.int32)]

    def requests():
        return [ServeRequest(p, max_new_tokens=3) for p in prompts]

    engine.generate(requests())  # compile prefill, decode, insert, evict
    cfg = tiny_dense()
    trainer = Trainer(build_model(cfg), TrainConfig(optimizer="lamb"),
                      log_every=2, log_fn=lambda s: None,
                      telemetry=EventLog.memory())
    batch = make_batch(cfg, np.random.default_rng(0), 2, 16)
    trainer.fit(itertools.repeat(batch), 2)
    pipe = DataPipeline(cfg, 2, 16, sharding=jax.devices()[0], prefetch=1)
    next(pipe)
    recorder = SpanRecorder()
    x = jnp.ones((8, 8))

    outdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(outdir)
    try:
        with trace_span("test.args", rid=7, slot=3):
            pass
        served = engine.generate(requests())
        trainer.fit(itertools.repeat(batch), 4)
        next(pipe)
        with recorder.span("interval", sync=x) as sp:
            sp.count = 3
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(glob.glob(f"{outdir}/plugins/profile/*/*.xplane.pb"))[-1]
    return {"events": _host_events(xplane), "served": served}


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_trace_span_args_land_as_stats(traced):
    (span,) = _named(traced["events"], "test.args")
    assert span[3]["rid"] == 7 and span[3]["slot"] == 3


def test_engine_admissions_nest_in_turns_with_their_rid(traced):
    ev = traced["events"]
    turns, admits = _named(ev, "serve.turn"), _named(ev, "serve.admit")
    rids = sorted(r.rid for r in traced["served"])
    assert sorted(a[3]["rid"] for a in admits) == rids
    for a in admits:
        assert any(_inside(a, t) for t in turns)
        for child in ("serve.prefill", "serve.sample", "serve.insert"):
            assert sum(_inside(c, a) for c in _named(ev, child)) == 1, child
    for t in turns:
        assert {"admitted", "active", "compiles"} <= set(t[3])
    assert sum(t[3]["admitted"] for t in turns) == len(rids)
    # both requests were admitted before the first decode step
    assert max(t[3]["active"] for t in turns) == 2


def test_engine_decode_steps_hold_their_phases(traced):
    ev = traced["events"]
    decodes = _named(ev, "serve.decode")
    # 3 tokens each: the prefill samples one, two decode steps the rest
    assert len(decodes) == 2
    for d in decodes:
        assert any(_inside(d, t) for t in _named(ev, "serve.turn"))
        for child in ("serve.device_state", "serve.dispatch", "serve.wait",
                      "serve.emit"):
            assert sum(_inside(c, d) for c in _named(ev, child)) == 1, child
    assert len(_named(ev, "serve.schedule")) == len(_named(ev, "serve.turn"))


def test_fit_loop_steps_hold_input_and_dispatch(traced):
    ev = traced["events"]
    steps = [e for e in ev if e[0].startswith("train.step")]
    assert sorted(e[3]["step_num"] for e in steps) == [0, 1, 2, 3]
    for s in steps:
        for child in ("train.input", "train.dispatch"):
            assert sum(_inside(c, s) for c in _named(ev, child)) == 1, child
    # the recorder's log interval (2 steps) is a span of its own
    assert len(_named(ev, "span.step")) == 2


def test_pipeline_names_its_source_and_placement(traced):
    ev = traced["events"]
    assert len(_named(ev, "data.next")) == 1
    assert len(_named(ev, "data.place")) == 1


def test_recorder_span_still_sums_and_is_traced(traced):
    (span,) = _named(traced["events"], "span.interval")
    assert span[3]["count"] == 3


def test_recorder_sums_and_emits_as_before():
    log = EventLog.memory()
    spans = SpanRecorder(log=log)
    spans.start("step")
    spans.start("step")  # re-opened: the first observation is dropped
    spans.stop("step", count=4)
    with spans.span("step") as sp:
        sp.count = 2
    s = spans.summary()["step"]
    assert s["count"] == 6
    assert [e["count"] for e in log.events] == [4, 2]


def test_compile_count_counts_new_compilations():
    x = jnp.ones(5, jnp.float32).block_until_ready()
    f = jax.jit(lambda x: x * 3 + 1)
    before = compile_count()
    f(x).block_until_ready()
    f(x).block_until_ready()  # no new compile
    assert compile_count() == before + 1


def test_step_events_and_serve_stats_carry_the_compile_count():
    log = EventLog.memory()
    cfg = tiny_dense()
    trainer = Trainer(build_model(cfg), TrainConfig(optimizer="lamb"),
                      log_every=1, log_fn=lambda s: None, telemetry=log)
    trainer.fit(itertools.repeat(make_batch(cfg, np.random.default_rng(0),
                                            2, 16)), 1)
    (step,) = [e for e in log.events if e["event"] == "step"]
    assert 0 < step["compiles"] <= compile_count()
    model = build_model(cfg)
    engine = ContinuousEngine(model, model.init(jax.random.key(0)),
                              n_slots=1, max_len=16, telemetry=log)
    engine.generate([ServeRequest(np.arange(1, 5, dtype=np.int32),
                                  max_new_tokens=2)])
    (stats,) = [e for e in log.events if e["event"] == "serve_stats"]
    assert stats["compiles"] == compile_count()


@pytest.mark.parametrize("fused", [False, True], ids=["chain", "fused"])
def test_train_step_scopes_name_forward_backward_and_optimizer(fused):
    cfg = tiny_dense()
    model = build_model(cfg)
    tc = TrainConfig(optimizer="lamb", use_fused_lamb=fused, precision="bf16",
                     skip_nonfinite=True, log_trust_ratios=True)
    init, step = make_train_step(model, tc)
    state = jax.eval_shape(init, jax.random.key(0))
    batch = jax.tree.map(jnp.asarray,
                         make_batch(cfg, np.random.default_rng(0), 2, 16))
    lowered = jax.jit(step).lower(state, batch)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    assert lowered.as_text().startswith("module @jit_step_fn")
    scoped = {"forward": [n for n in names if "jvp(model)" in n
                          and "transpose(" not in n],
              "backward": [n for n in names if "transpose(jvp(model))" in n],
              "optimizer": [n for n in names if "/optimizer/" in n],
              "cast": [n for n in names if "/cast_params/" in n]}
    for part, found in scoped.items():
        assert found, part
    # the guard and the trust diagnostics come after the gradients
    assert any(n.endswith("/is_finite") for n in scoped["optimizer"])
