"""The traffic generator: the same seed gives the same inputs, and the
steady mix has Poisson arrivals at its rate and the stated length
distributions."""
from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

from chipbench import BENCH_DIR, REPO
from chipbench import traffic as tf

STEADY = json.loads((BENCH_DIR / "traffic/serve.steady.json").read_text())
SMOL = json.loads((BENCH_DIR / "configs/smollm-360m.json").read_text())
BERT = json.loads((BENCH_DIR / "configs/bert-large.json").read_text())
T128 = json.loads((BENCH_DIR / "traffic/train.seq128.json").read_text())
RUN_S = json.loads((REPO / "BENCHMARK.json").read_text())["run_seconds"]
BIG_SEED = 2**31 + 987654321


def _schedule(seed):
    return tf.serve_schedule(STEADY, SMOL, seed, RUN_S)


def test_serve_schedule_is_deterministic_per_seed():
    a, b, c = _schedule(BIG_SEED), _schedule(BIG_SEED), _schedule(BIG_SEED + 1)
    assert [(x.arrival_s, x.max_new_tokens, x.prompt.tolist()) for x in a] \
        == [(x.arrival_s, x.max_new_tokens, x.prompt.tolist()) for x in b]
    assert [x.arrival_s for x in a] != [x.arrival_s for x in c]


def test_steady_window_holds_at_least_200_arrivals_at_the_rate():
    window = [x for x in _schedule(BIG_SEED) if x.phase == 1]
    assert len(window) == round(STEADY["rate_per_s"] * RUN_S) >= 200
    t = [x.arrival_s for x in window]
    ramp = STEADY["ramp_s"]
    assert ramp <= min(t) and max(t) < ramp + RUN_S


def test_seeds_reorder_the_same_sizes():
    a, b = _schedule(1), _schedule(2)
    assert Counter(len(x.prompt) for x in a) == Counter(len(x.prompt) for x in b)
    assert Counter(x.max_new_tokens for x in a) == \
        Counter(x.max_new_tokens for x in b)
    assert [x.max_new_tokens for x in a] != [x.max_new_tokens for x in b]
    assert [x.arrival_s for x in a] != [x.arrival_s for x in b]


def test_steady_arrivals_are_poisson():
    """Over many seeds' windows: counts a second have their variance equal
    to their mean, and gaps are exponential (standard deviation = mean)."""
    ramp, rate = STEADY["ramp_s"], STEADY["rate_per_s"]
    counts, gaps = [], []
    for seed in range(40):
        t = np.array([x.arrival_s for x in _schedule(BIG_SEED + seed)
                      if x.phase == 1])
        counts += list(np.histogram(t, np.arange(ramp, ramp + RUN_S + 0.5))[0])
        gaps += list(np.diff(t))
    counts, gaps = np.array(counts), np.array(gaps)
    assert abs(counts.mean() - rate) < 0.05 * rate
    assert abs(counts.var() / counts.mean() - 1.0) < 0.1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05
    # no smoothing: some seconds carry twice the rate, some none
    assert counts.max() >= 2 * rate and counts.min() <= 1


def test_steady_lengths_follow_the_stated_distributions():
    g = np.random.default_rng(0)
    n = 20000
    prompts = tf.lognormal_lengths(g, n, STEADY["prompt"])
    outputs = tf.lognormal_lengths(g, n, STEADY["output"])
    assert set(prompts) <= set(STEADY["prompt"]["snap"])
    assert abs(np.mean(prompts <= 128) - 0.5) < 0.02
    assert prompts.min() == 16 and prompts.max() == 1024
    assert abs(np.mean(outputs <= 32) - 0.5) < 0.02
    assert 1 <= outputs.min() and outputs.max() == 256
    # mean of a lognormal with median 32, sigma 0.8: 32 * exp(0.32) = 44.1
    assert abs(outputs.mean() - 44.1) < 1.5
    # the window's own draw, which every seed shares
    window = [x for x in _schedule(BIG_SEED) if x.phase == 1]
    assert abs(np.mean([x.max_new_tokens for x in window]) - 44.1) < 4.0


def test_mlm_batches_are_deterministic_and_mask_bert_style():
    a = tf.first_batches(T128, BERT, BIG_SEED, 2)
    b = tf.first_batches(T128, BERT, BIG_SEED, 2)
    for x, y in zip(a, b):
        assert (x["tokens"] == y["tokens"]).all()
        assert (x["labels"] == y["labels"]).all()
    batch = a[0]
    assert batch["tokens"].shape == (32, 128)
    per_row = (batch["labels"] >= 0).sum(axis=1)
    assert (per_row == tf.predictions_per_row(T128)).all()
    assert tf.predictions_per_row(T128) == 19
    masked = batch["tokens"][batch["labels"] >= 0]
    share = (masked == T128["mask_token_id"]).mean()
    assert 0.7 < share < 0.9
    # no two rows repeat, within or across batches
    rows = {r.tobytes() for x in a for r in x["tokens"]}
    assert len(rows) == 64


def test_causal_lm_batches_are_deterministic_and_shift_by_one():
    lm = {k: v for k, v in T128.items()
          if k not in ("mask_prob", "max_predictions", "mask_token_id")}
    lm["objective"] = "causal_lm"
    a = tf.first_batches(lm, SMOL, BIG_SEED, 2)
    b = tf.first_batches(lm, SMOL, BIG_SEED, 2)
    c = tf.first_batches(lm, SMOL, BIG_SEED + 1, 1)
    for x, y in zip(a, b):
        assert (x["tokens"] == y["tokens"]).all()
        assert (x["labels"] == y["labels"]).all()
    assert (a[0]["tokens"] != c[0]["tokens"]).any()
    batch = a[0]
    assert batch["tokens"].shape == batch["labels"].shape == (32, 128)
    # labels[t] is tokens[t + 1]; the last position is not predicted
    assert (batch["labels"][:, :-1] == batch["tokens"][:, 1:]).all()
    assert (batch["labels"][:, -1] == tf.IGNORE).all()
    assert tf.predictions_per_row(lm) == 127
    assert batch["tokens"].min() >= lm["first_token_id"]
    assert batch["tokens"].max() < SMOL["vocab_size"]
    rows = {r.tobytes() for x in a for r in x["tokens"]}
    assert len(rows) == 64


def test_a_training_mix_names_a_known_objective():
    assert T128["objective"] == "mlm"
    with pytest.raises(ValueError, match="'next_sentence'"):
        tf.first_batches(dict(T128, objective="next_sentence"), BERT, 1, 1)
    with pytest.raises(KeyError, match="objective"):
        tf.predictions_per_row({k: v for k, v in T128.items()
                                if k != "objective"})
