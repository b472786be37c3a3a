"""End-to-end runs of the harness on the CPU at a tiny size.

Each test skips the harness's look for a chip and drives the rest of a
run: set-up, window, reference and result line.  With the timed path
broken underneath, ``correct`` must come out false.
"""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from chipbench import common as cm
from chipbench import tiny
from chipbench.control import fp8_matmul
from chipbench.spec import Cell

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    return root, tiny.build(root)


def cell(tree, name):
    root, bench = tree
    return Cell(name, repo=root, bench_dir=bench)


def run_cell(tree, name, trace=False, capsys=None, **hooks):
    import importlib

    c = cell(tree, name)
    runner = importlib.import_module(
        {"train": "chipbench.train", "serve": "chipbench.serve"}
        [c.traffic["kind"]])
    res = runner.run(c, 2**31 + 12345, 0.5, trace, cm.now(), jax.devices(),
                     **hooks)
    cm.emit(**res)
    if capsys is not None:
        return c, json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return c, res


def test_train_result_line_has_the_contract_keys(tree, capsys):
    c, line = run_cell(tree, tiny.TRAIN, capsys=capsys)
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {m["name"] for m in
                                    c.metrics("end_to_end")}
    assert line["device"]["count"] == 1 and line["attempted"] > 0


def test_lm_train_result_line_has_the_contract_keys(tree, capsys):
    """A decoder trained next-token, added by files alone, runs end to end
    and is correct."""
    c, line = run_cell(tree, tiny.LM_TRAIN, capsys=capsys)
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert c.traffic["objective"] == "causal_lm"


def test_serve_result_line_has_the_contract_keys(tree, capsys):
    c, line = run_cell(tree, tiny.SERVE, capsys=capsys)
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"serve_ttft_p95_ms", "serve_itl_p95_ms",
                                    "serve_tokens_per_s", "setup_s"}
    assert line["attempted"] == round(200.0 * 0.5)


def test_traced_train_run_reports_per_layer_metrics(tree):
    _, res = run_cell(tree, tiny.TRAIN, trace=True)
    # the CPU has no TPU device plane: trace metrics stay silent, the
    # host-clock share is still read
    assert "train_mfu" in res["metrics"]
    assert "flash_roofline" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def _unchanged(step):
    def broken(state, batch):
        _, metrics = step(jax.tree.map(lambda x: x.copy(), state), batch)
        return state, metrics
    return broken


def _half_batch(step):
    def broken(state, batch):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return step(state, half)
    return broken


def _labels_not_shifted(step):
    def broken(state, batch):
        return step(state, dict(batch, labels=batch["tokens"]))
    return broken


@pytest.mark.parametrize("name,hook", [
    (tiny.TRAIN, _unchanged), (tiny.TRAIN, _half_batch),
    (tiny.LM_TRAIN, _unchanged), (tiny.LM_TRAIN, _half_batch),
    (tiny.LM_TRAIN, _labels_not_shifted)],
    ids=["state_unchanged", "half_batch", "lm-state_unchanged",
         "lm-half_batch", "lm-labels_not_shifted"])
def test_train_fault_is_not_correct(tree, name, hook):
    _, res = run_cell(tree, name, step_hook=hook)
    assert res["correct"] is False, res["checks"]


def _altered_token(engine):
    inner = engine._decode_greedy
    calls = {"n": 0}

    def broken(*args):
        toks, pos, cache = inner(*args)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            toks = (toks + 1) % 4096
        return toks, pos, cache

    engine._decode_greedy = broken


def test_serve_altered_token_is_not_correct(tree):
    _, res = run_cell(tree, tiny.SERVE, token_hook=_altered_token)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", [tiny.TRAIN, tiny.LM_TRAIN])
def test_train_precision_control_fails_the_limits(tree, name):
    from chipbench import train as T

    c = cell(tree, name)
    seed = 7
    ref = T.reference_readings(c, seed)
    control = T.readings(T.reference_readings(c, seed, mm=fp8_matmul), ref)
    ok, checks = cm.judge(control, c.limits)
    assert not ok, checks


def test_serve_precision_control_fails_the_limits(tree):
    from chipbench import serve as S

    c = cell(tree, tiny.SERVE)
    prog = S.Program(c, 11)
    prog.warm_up()
    rec = S.run_window(prog, 11, 0.5)
    sample = S.check_sample(rec, 11, c.traffic["check_tokens"])
    served = S.served_gaps(c, 11, sample)
    control = S.served_gaps(c, 11, sample, lower=fp8_matmul)
    assert served["served_logit_gap"] <= c.limits["served_logit_gap"]
    assert control["served_logit_gap"] > c.limits["served_logit_gap"]
    assert np.isfinite(control["served_logit_gap"])


FOUR_CHIPS = r'''
import json, sys
import jax
from chipbench import common as cm, tiny, train
from chipbench.spec import Cell
from pathlib import Path

root = Path(sys.argv[1])
bench = tiny.build(root)
cell = Cell(tiny.TRAIN4, repo=root, bench_dir=bench)


def no_exchange(step):
    # each chip's update from its own quarter of the batch alone: the
    # mean over four copies of one quarter is that quarter's mean
    def broken(state, batch):
        def quarter(x):
            q = x[: x.shape[0] // 4]
            return jax.device_put(jax.numpy.concatenate([q] * 4), x.sharding)
        return step(state, jax.tree.map(quarter, batch))
    return broken


out = {}
for name, hook in (("sound", None), ("no_exchange", no_exchange)):
    res = train.run(cell, 2**31 + 5, 0.5, False, cm.now(), jax.devices(),
                    step_hook=hook)
    out[name] = [res["correct"], res["device"]["count"], res["checks"]]
print(json.dumps(out))
'''


def test_four_chip_run_and_its_missing_exchange(tmp_path):
    """On four virtual CPU devices: a sound data=4 run is correct, one
    whose chips each update from their own quarter of the batch is not."""
    import os
    import subprocess
    import sys

    from chipbench import BENCH_DIR, REPO

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(BENCH_DIR), str(REPO / "src")]))
    proc = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][:2] == [True, 4], out
    assert out["no_exchange"][0] is False, out


def test_a_mesh_step_runs_under_the_trainers_sharding_context():
    """On a mesh the step is traced where ``Trainer.fit`` traces it: under
    the trainer's sharding context, so the model's activation rules hold."""
    from jax.sharding import Mesh

    from chipbench.train import in_sharding
    from repro.sharding.context import ShardCtx, current

    ctx = ShardCtx(Mesh(np.array(jax.devices()[:1]), ("data",)))
    seen = in_sharding(lambda state, batch: current(), ctx)(None, None)
    assert seen is ctx and current() is None
