"""Telemetry subsystem: event schema, span timers, trust-ratio recorder,
serve counters, and the regression-gated run report.

The two load-bearing guarantees:

* **zero-overhead null sink** — with telemetry off the Trainer's metrics
  history is identical (modulo wall-clock fields) to a telemetry-on run's,
  and the step function contains no extra host syncs;
* **recorder ≡ oracle** — the per-layer trust ratios threaded out of the
  fused-LAMB kernels match a hand-computed numpy ``phi(||w||)/||u||`` at
  step 1 from zero moments, and the unfused recorder matches the post-hoc
  ``phi(||w||)/||Δw||`` diagnostic recomputed from the actual deltas.
"""
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs.base import TrainConfig
from repro.data import make_batch
from repro.kernels import fused_lamb_init, make_fused_lamb_step
from repro.models import build_model
from repro.telemetry import (
    EVENT_TYPES,
    EventLog,
    RunReport,
    SpanRecorder,
    TrustRecorder,
    read_events,
    run_provenance,
    validate_event,
)
from repro.telemetry.trust import PER_LAYER_KEY
from repro.train import Trainer, make_train_step
from tests.conftest import tiny_dense


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_event_log_jsonl_roundtrip(tmp_path):
    log = EventLog.to_dir(tmp_path)
    log.emit("run_start", provenance=run_provenance(), arch="tiny")
    log.emit("step", step=10, metrics={"loss/total": 1.5})
    log.emit("span", name="step", seconds=0.25, count=10)
    log.emit("checkpoint", step=10, path=str(tmp_path))
    log.emit("run_end", status="ok")
    log.close()

    events = read_events(tmp_path / "events.jsonl")
    assert [e["event"] for e in events] == [
        "run_start", "step", "span", "checkpoint", "run_end"]
    assert [e["seq"] for e in events] == list(range(5))
    assert events[1]["metrics"]["loss/total"] == 1.5
    assert events[0]["provenance"]["git_sha"]
    # appended, not truncated: a second log continues the file
    log2 = EventLog(tmp_path / "events.jsonl")
    log2.emit("run_end", status="again")
    log2.close()
    assert len(read_events(tmp_path / "events.jsonl")) == 6


def test_event_schema_rejects_bad_events():
    log = EventLog.memory()
    with pytest.raises(ValueError, match="unknown event type"):
        log.emit("not_a_type", anything=1)
    with pytest.raises(ValueError, match="missing required fields"):
        log.emit("span", name="no-seconds")
    with pytest.raises(ValueError, match="missing required fields"):
        log.emit("run_start")  # no provenance
    for etype in EVENT_TYPES:
        # every type's required fields are themselves valid
        fields = {f: 0 for f in
                  __import__("repro.telemetry.events",
                             fromlist=["REQUIRED_FIELDS"]).REQUIRED_FIELDS[etype]}
        validate_event({"event": etype, **fields})


def test_null_sink_is_noop(tmp_path):
    log = EventLog()
    assert not log.enabled
    # emit never validates or serializes: junk args must not raise
    assert log.emit("not_even_a_type", junk=object()) is None
    assert log.events == []
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# span timers
# ---------------------------------------------------------------------------

def test_span_timer_syncs_async_dispatch():
    spans = SpanRecorder(log=EventLog.memory())
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    float(f(x))  # compile outside any span

    with spans.span("mm", sync=x) as sp:
        out = None
        for _ in range(4):
            out = f(x)
        sp.block_on(out)
        sp.count = 4
    s = spans.summary()["mm"]
    assert s["count"] == 4
    assert s["total_s"] > 0
    assert s["mean_s"] == pytest.approx(s["total_s"] / 4)
    ev = spans.log.events[0]
    assert ev["event"] == "span" and ev["count"] == 4


def test_span_phase_style_and_errors():
    spans = SpanRecorder()
    spans.start("step")
    dt = spans.stop("step", count=2)
    assert dt >= 0
    with pytest.raises(ValueError, match="never started"):
        spans.stop("step")
    assert spans.summary()["step"]["count"] == 2


# ---------------------------------------------------------------------------
# trust-ratio recorder vs hand-computed oracles
# ---------------------------------------------------------------------------

def _lamb_oracle_ratio(x, g, *, eps, wd, layer_axis=None):
    """numpy phi(||w||)/||u|| at step 1 from zero moments (bias-corrected:
    m_hat = g, sqrt(v_hat) = |g|)."""
    x = np.asarray(x, np.float64)
    g = np.asarray(g, np.float64)
    r = g / (np.abs(g) + eps)
    u = r + wd * x
    if layer_axis is None:
        axes = tuple(range(x.ndim))
    else:
        axes = tuple(i for i in range(x.ndim) if i != layer_axis)
    w_norm = np.sqrt((x * x).sum(axis=axes))
    u_norm = np.sqrt((u * u).sum(axis=axes))
    return w_norm / u_norm


def test_fused_aux_ratio_matches_numpy_oracle():
    """The kernel's threaded-out aux ratio IS the applied ratio — checked
    against a from-scratch numpy LAMB on a stacked + unstacked leaf pair."""
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    grads = {"w": jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    eps, wd = 1e-6, 0.01
    step = make_fused_lamb_step(
        0.1, 0.9, 0.999, eps, wd,
        wd_mask={"w": True, "b": False},
        trust_mask={"w": True, "b": False},
        layer_axes={"w": 0, "b": None},
        grad_clip_norm=None, mode="xla", with_aux=True,
    )
    _, _, _, trust = jax.jit(step)(params, grads, fused_lamb_init(params))

    want_w = _lamb_oracle_ratio(params["w"], grads["w"], eps=eps, wd=wd,
                                layer_axis=0)
    np.testing.assert_allclose(
        np.asarray(trust["w"]).reshape(-1), want_w, rtol=1e-5)
    # trust-masked leaf: applied ratio is identically 1
    np.testing.assert_allclose(np.asarray(trust["b"]).reshape(-1), 1.0)


def test_fused_step_records_applied_ratio_per_layer():
    """End-to-end through make_train_step: the recorded per-layer ratio on a
    2-layer stacked model equals the step-1 oracle computed from the step's
    own gradients."""
    cfg = tiny_dense()
    model = build_model(cfg)
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True,
                     record_trust_ratios=True, grad_clip_norm=None)
    init_fn, step_fn = make_train_step(model, tc)
    state = init_fn(jax.random.key(0))
    batch = jax.tree.map(jnp.asarray,
                         make_batch(cfg, np.random.default_rng(0), 2, 16))
    _, metrics = jax.jit(step_fn)(state, batch)
    rec = jax.device_get(metrics[PER_LAYER_KEY])

    # oracle from the very gradients the step consumed
    from repro.train.step import make_loss_fn
    grads = jax.grad(lambda p: make_loss_fn(model)(p, batch)[0])(state.params)
    axes = model.layer_axes()
    wd_mask, trust_mask = model.wd_mask(), model.trust_mask()

    def oracle(x, g, ax, wd_on, trust_on):
        ax = None if ax is None or ax < 0 else ax  # -1 = unstacked
        if not trust_on:
            return np.ones(np.asarray(x).shape[ax] if ax is not None else ())
        return _lamb_oracle_ratio(
            x, g, eps=tc.eps, wd=tc.weight_decay if wd_on else 0.0,
            layer_axis=ax)

    want = jax.tree.map(oracle, state.params, grads, axes, wd_mask, trust_mask)
    for got, exp in zip(jax.tree.leaves(rec["trust_ratio"]),
                        jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got).reshape(-1),
                                   np.asarray(exp).reshape(-1), rtol=2e-4)
    # param/update norms ride along, same tree structure
    assert (jax.tree.structure(rec["param_norm"])
            == jax.tree.structure(rec["trust_ratio"]))


def test_unfused_records_match_posthoc_norms():
    """Transform-chain path: recorded ratio == phi(||w||)/||Δw|| recomputed
    from the actual parameter deltas, per layer slice."""
    cfg = tiny_dense()
    model = build_model(cfg)
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3,
                     record_trust_ratios=True)
    init_fn, step_fn = make_train_step(model, tc)
    state = init_fn(jax.random.key(0))
    batch = jax.tree.map(jnp.asarray,
                         make_batch(cfg, np.random.default_rng(0), 2, 16))
    new_state, metrics = jax.jit(step_fn)(state, batch)
    rec = jax.device_get(metrics[PER_LAYER_KEY])
    axes = model.layer_axes()

    def slice_norm(x, ax):
        x = np.asarray(x, np.float64)
        if ax is None or ax < 0:  # -1 = unstacked
            return np.sqrt((x * x).sum())
        other = tuple(i for i in range(x.ndim) if i != ax)
        return np.sqrt((x * x).sum(axis=other))

    for got_r, got_p, old, new, ax in zip(
            jax.tree.leaves(rec["trust_ratio"]),
            jax.tree.leaves(rec["param_norm"]),
            jax.tree.leaves(state.params),
            jax.tree.leaves(new_state.params),
            jax.tree.leaves(axes, is_leaf=lambda x: x is None)):
        w = slice_norm(old, ax)
        d = slice_norm(np.asarray(new) - np.asarray(old), ax)
        np.testing.assert_allclose(np.asarray(got_r).reshape(-1),
                                   np.atleast_1d(w / d), rtol=2e-4)
        np.testing.assert_allclose(np.asarray(got_p).reshape(-1),
                                   np.atleast_1d(w), rtol=2e-4)


def test_trust_recorder_histogram_and_summary():
    rec = TrustRecorder(log=EventLog.memory())
    records = {"trust_ratio": {"a": np.array([0.5, 2.0]), "b": np.array(1.0)},
               "param_norm": {"a": np.array([1.0, 1.0]), "b": np.array(3.0)},
               "update_norm": {"a": np.array([2.0, 0.5]), "b": np.array(3.0)}}
    layers = rec.record(10, records)
    assert layers["a"]["per_layer"] == [0.5, 2.0]
    assert layers["b"]["param_norm"] == [3.0]
    s = rec.summary()
    assert s["steps_recorded"] == 1
    assert s["per_leaf"]["a"] == {"min": 0.5, "max": 2.0, "mean": 1.25}
    assert sum(s["hist"]["counts"]) == 3  # every ratio landed in a bin
    ev = rec.log.events[0]
    assert ev["event"] == "trust_ratios" and ev["step"] == 10


# ---------------------------------------------------------------------------
# trainer integration: zero-overhead null sink + emitted events
# ---------------------------------------------------------------------------

def _fit_tiny(telemetry=None, steps=4, **tc_kw):
    cfg = tiny_dense()
    model = build_model(cfg)
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, **tc_kw)
    tr = Trainer(model, tc, log_every=2, log_fn=lambda s: None,
                 telemetry=telemetry)
    batch = make_batch(cfg, np.random.default_rng(0), 2, 16)
    tr.fit(itertools.repeat(batch), steps)
    return tr


TIMING_KEYS = {"wall_s"}  # legitimately differs run-to-run


def test_history_identical_with_telemetry_off_vs_on():
    h_off = _fit_tiny(telemetry=None).history
    h_on = _fit_tiny(telemetry=EventLog.memory()).history
    assert len(h_off) == len(h_on)
    for a, b in zip(h_off, h_on):
        assert set(a) == set(b)
        for k in a:
            if k not in TIMING_KEYS:
                assert a[k] == b[k], k


def test_trainer_emits_run_events():
    log = EventLog.memory()
    tr = _fit_tiny(telemetry=log, use_fused_lamb=True,
                   record_trust_ratios=True, log_trust_ratios=True)
    types = [e["event"] for e in log.events]
    assert types[0] == "run_start"
    prov = log.events[0]["provenance"]
    for k in ("git_sha", "jax_version", "device_kind", "config_hash"):
        assert k in prov, k
    assert types.count("step") == 2      # 4 steps, log_every=2
    assert types.count("span") == 2      # one per logged interval
    assert types.count("trust_ratios") == 2
    step_ev = next(e for e in log.events if e["event"] == "step")
    assert step_ev["step_time_s"] > 0
    assert "loss/total" in step_ev["metrics"]
    # per-layer records were popped out of the scalar history
    assert all(PER_LAYER_KEY not in h for h in tr.history)


def test_fit_stages_history_carries_wall_s():
    cfg = tiny_dense()
    model = build_model(cfg)
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3)
    log = EventLog.memory()
    tr = Trainer(model, tc, log_every=1, log_fn=lambda s: None, telemetry=log)
    stages = [
        core.make_stage("s1", 16, 4, 2, base_lr=1e-3, base_batch=4,
                        base_warmup_ratio=0.25),
        core.make_stage("s2", 32, 2, 2, base_lr=1e-3, base_batch=4,
                        base_warmup_ratio=0.25),
    ]
    hist = tr.fit_stages(stages)
    walls = [h["wall_s"] for h in hist]
    assert len(walls) == 4 and all(w > 0 for w in walls)
    assert walls == sorted(walls)  # one clock across stages, monotone
    assert [e["name"] for e in log.events
            if e["event"] == "stage_start"] == ["s1", "s2"]


# ---------------------------------------------------------------------------
# serve counters
# ---------------------------------------------------------------------------

def test_serve_counters_from_continuous_engine():
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.scheduler import ServeRequest

    cfg = tiny_dense()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    log = EventLog.memory()
    eng = ContinuousEngine(model, params, n_slots=2, max_len=32, telemetry=log)
    reqs = [ServeRequest(prompt=np.arange(1, 5, dtype=np.int32),
                         max_new_tokens=3) for _ in range(3)]
    # a request already past its deadline on arrival must be dropped + logged
    reqs.append(ServeRequest(prompt=np.arange(1, 5, dtype=np.int32),
                             max_new_tokens=3, arrival_s=0.0, deadline_s=-1.0))
    out = eng.generate(reqs)

    sr = [e for e in log.events if e["event"] == "serve_request"]
    assert len(sr) == 4
    dropped = [e for e in sr if e["dropped"]]
    assert len(dropped) == 1 and dropped[0]["new_tokens"] == 0
    for e in sr:
        if not e["dropped"]:
            assert e["new_tokens"] == 3
            assert e["latency_s"] >= e["ttft_s"] >= 0

    stats = [e for e in log.events if e["event"] == "serve_stats"]
    assert len(stats) == 1
    st = stats[0]
    assert st["requests"] == 3 and st["dropped"] == 1
    assert st["n_slots"] == 2 and 0 < st["slot_occupancy_mean"] <= 1
    assert st["decode_steps"] > 0 and st["queue_depth_max"] >= 1
    assert sum(1 for r in out if r.dropped) == 1


# ---------------------------------------------------------------------------
# run report + regression gate
# ---------------------------------------------------------------------------

def _report_from_tiny_run():
    log = EventLog.memory()
    _fit_tiny(telemetry=log, use_fused_lamb=True, record_trust_ratios=True,
              log_trust_ratios=True)
    log.emit("run_end", status="ok")
    return RunReport.from_events(log)


def test_run_report_sections_and_io(tmp_path):
    rep = _report_from_tiny_run()
    for section in ("provenance", "train", "spans", "trust_ratios",
                    "run_end", "events"):
        assert section in rep.report, section
    assert rep.report["train"]["logged_steps"] == 2
    assert rep.report["train"]["final"]["loss/total"] > 0
    assert rep.report["trust_ratios"]["per_leaf"]
    assert sum(rep.report["trust_ratios"]["hist"]["counts"]) > 0
    p = rep.write(tmp_path / "RUN_REPORT.json")
    loaded = RunReport.load(p)
    assert loaded.report == json.loads(json.dumps(rep.report))


def test_run_report_compare_passes_within_tolerance():
    rep = _report_from_tiny_run()
    base = json.loads(json.dumps(rep.report))
    base["train"]["final"]["loss/total"] *= 1.01  # 1% off, 5% tol
    res = rep.compare(base, {
        "train.final.loss/total": 0.05,
        "train.logged_steps": 0.0,
        "spans.step.mean_s": None,        # presence only: timing drifts
        "provenance.jax_version": 0.0,    # non-numeric: exact equality
    })
    assert res.ok, res.render()
    assert "PASS" in res.render()


def test_run_report_compare_fails_on_regression_and_schema():
    rep = _report_from_tiny_run()
    base = json.loads(json.dumps(rep.report))
    base["train"]["final"]["loss/total"] *= 2.0
    base["serve"] = {"requests": 1}  # baseline section this report lacks
    res = rep.compare(base, {
        "train.final.loss/total": 0.05,
        "no.such.key": None,
    })
    assert not res.ok
    statuses = {c.key: c.status for c in res.checks}
    assert statuses["train.final.loss/total"] == "regressed"
    assert statuses["section:serve"] == "missing"
    assert statuses["no.such.key"] == "missing"
    assert "FAIL" in res.render()


def test_run_report_folds_bench_json(tmp_path):
    (tmp_path / "BENCH_demo.json").write_text(
        json.dumps({"holds": True, "provenance": {"git_sha": "abc"}}))
    log = EventLog.memory()
    log.emit("run_start", provenance=run_provenance(), mode="bench")
    log.emit("bench_result", name="demo", ok=True, rows=3)
    log.emit("run_end", status="ok")
    rep = RunReport.from_events(log, bench_dir=tmp_path)
    assert rep.report["bench"]["demo"]["ok"] is True
    assert rep.report["bench"]["demo"]["json"]["holds"] is True
