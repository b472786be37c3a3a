"""The one traffic generator.  A mix is a data file under ``traffic/``;
everything drawn here comes from ``--seed`` and nothing else.

Training mixes (``"kind": "train"``) give a stream of batches for the
mix's ``objective``: BERT masked-LM (``mlm``) or next-token prediction
(``causal_lm``).  Serving mixes (``"kind": "serve"``) give an open-loop
schedule of greedy requests: Poisson arrivals, with independent lognormal
prompt and output lengths whose draw every seed shares and deals out in
its own order, so that seeds change the order of the work and not its
amount.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List

import numpy as np

MASK_CHANCE, RANDOM_CHANCE = 0.8, 0.1  # BERT: 80% [MASK], 10% random, 10% kept
IGNORE = -1  # the label of a position that is not predicted


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (for JAX keys)."""
    return np.random.SeedSequence(seed % 2**64).generate_state(2)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream])


# ---------------------------------------------------------------------------
# training: masked-LM or next-token batches
# ---------------------------------------------------------------------------

def objective(traffic: dict) -> str:
    obj = traffic["objective"]
    if obj not in TRAIN_BATCHES:
        raise ValueError(f"unknown training objective {obj!r} (known: "
                         f"{sorted(TRAIN_BATCHES)})")
    return obj


def predictions_per_row(traffic: dict) -> int:
    """Predicted positions of a row.  MLM: BERT's count, round(seq *
    mask_prob), at least 1, at most ``max_predictions``.  Next-token: every
    position but the last."""
    s = traffic["seq_len"]
    if objective(traffic) == "causal_lm":
        return s - 1
    return min(traffic["max_predictions"],
               max(1, int(round(s * traffic["mask_prob"]))))


class ZipfTokens:
    """Token ids with a Zipf marginal over the vocabulary (ids below
    ``first_id`` are special and never drawn)."""

    def __init__(self, vocab: int, a: float, first_id: int):
        ranks = np.arange(1, vocab - first_id + 1, dtype=np.float64)
        p = ranks ** -a
        self.cdf = np.cumsum(p / p.sum())
        self.first_id = first_id

    def draw(self, g: np.random.Generator, shape) -> np.ndarray:
        idx = np.searchsorted(self.cdf, g.random(shape), side="right")
        return (np.minimum(idx, len(self.cdf) - 1)
                + self.first_id).astype(np.int32)


def mlm_batches(traffic: dict, cfg: dict,
                seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless masked-LM batches of ``global_batch`` rows.

    Each row masks the same number of positions, chosen uniformly; every
    row of every batch is drawn afresh, so no two rows repeat."""
    b = traffic["global_batch"]
    s = traffic["seq_len"]
    n_pred = predictions_per_row(traffic)
    vocab = cfg["vocab_size"]
    toks = ZipfTokens(vocab, traffic["zipf_a"], traffic["first_token_id"])
    g = rng(seed, 1)
    while True:
        tokens = toks.draw(g, (b, s))
        pos = np.argsort(g.random((b, s)), axis=1)[:, :n_pred]
        rows_ix = np.arange(b)[:, None]
        labels = np.full((b, s), IGNORE, np.int32)
        labels[rows_ix, pos] = tokens[rows_ix, pos]
        u = g.random((b, n_pred))
        picked = tokens[rows_ix, pos]
        swapped = np.where(u < MASK_CHANCE, traffic["mask_token_id"],
                           np.where(u < MASK_CHANCE + RANDOM_CHANCE,
                                    toks.draw(g, (b, n_pred)), picked))
        tokens = tokens.copy()
        tokens[rows_ix, pos] = swapped
        yield {"tokens": tokens, "labels": labels}


def causal_lm_batches(traffic: dict, cfg: dict,
                      seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless next-token batches of ``global_batch`` rows: each row an
    unbroken stream of ``seq_len`` tokens drawn afresh, ``labels[t] =
    tokens[t + 1]`` and the last position not predicted."""
    b = traffic["global_batch"]
    s = traffic["seq_len"]
    toks = ZipfTokens(cfg["vocab_size"], traffic["zipf_a"],
                      traffic["first_token_id"])
    g = rng(seed, 1)
    while True:
        tokens = toks.draw(g, (b, s))
        labels = np.full((b, s), IGNORE, np.int32)
        labels[:, :-1] = tokens[:, 1:]
        yield {"tokens": tokens, "labels": labels}


TRAIN_BATCHES = {"mlm": mlm_batches, "causal_lm": causal_lm_batches}


def train_batches(traffic: dict, cfg: dict,
                  seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """The mix's endless stream of training batches."""
    return TRAIN_BATCHES[objective(traffic)](traffic, cfg, seed)


def first_batches(traffic: dict, cfg: dict, seed: int, n: int) -> List[dict]:
    it = train_batches(traffic, cfg, seed)
    return [next(it) for _ in range(n)]


# ---------------------------------------------------------------------------
# serving: an open-loop request schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Arrival:
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int
    phase: int              # 0 ramp, 1 measured window, 2 tail


def lognormal_lengths(g: np.random.Generator, n: int,
                      spec: dict) -> np.ndarray:
    """n independent lognormal lengths, rounded up, clipped to [min, max]
    and, with ``snap``, raised to the next allowed length."""
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * g.standard_normal(n))
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    if "snap" in spec:
        allowed = np.array(sorted(spec["snap"]))
        x = allowed[np.searchsorted(allowed, x)]
    return x


def poisson_times(g: np.random.Generator, n: int, start: float,
                  span: float) -> np.ndarray:
    """The arrival times of a Poisson process over [start, start + span)
    given that n arrive there: n independent uniform points, sorted.  The
    gaps between them are independent exponentials up to the count."""
    return start + np.sort(g.random(n)) * span


def phase_bounds(traffic: dict, seconds: float) -> List[tuple]:
    """(start, length) of the ramp, the measured window and the tail."""
    ramp, tail = traffic["ramp_s"], traffic["tail_s"]
    return [(0.0, ramp), (ramp, float(seconds)), (ramp + seconds, tail)]


def serve_schedule(traffic: dict, cfg: dict, seed: int,
                   seconds: float) -> List[Arrival]:
    """Poisson arrivals at ``rate_per_s`` over the ramp, the window and the
    tail, round(rate * length) in each, so every window of one length holds
    the same number of arrivals.  Each phase's prompt and output lengths
    are one independent lognormal draw fixed by the mix's ``sizes_seed``,
    dealt out in an order drawn from ``seed``: seeds change when the work
    comes and in what order, not how much of it there is."""
    g = rng(seed, 2)
    rate = traffic["rate_per_s"]
    out: List[Arrival] = []
    for phase, (start, span) in enumerate(phase_bounds(traffic, seconds)):
        n = int(round(rate * span))
        if n == 0:
            continue
        sizes = rng(traffic["sizes_seed"], 10 + phase)
        plen = lognormal_lengths(sizes, n, traffic["prompt"])
        olen = lognormal_lengths(sizes, n, traffic["output"])
        order = g.permutation(n)
        times = poisson_times(g, n, start, span)
        for t, p, o in zip(times, plen[order], olen[order]):
            prompt = g.integers(0, cfg["vocab_size"], int(p)).astype(np.int32)
            out.append(Arrival(float(t), prompt, int(o), phase))
    return out
