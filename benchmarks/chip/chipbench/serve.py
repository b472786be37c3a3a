"""Serving cells: greedy requests through ``ContinuousEngine`` with
``FCFSScheduler``, offered open loop at the mix's fixed rate.

Set-up draws the weights on the device from the seed (bfloat16, as
served), builds the engine, and warms up every shape the mix uses (one
prefill per allowed prompt length, the decode step, slot insert and
evict).  The whole schedule is then submitted and ``generate`` runs it:
a ramp at the full rate first (counted in set-up), then the measured
window, then arrivals go on until every request that arrived in the
window has its first token, and ``should_drain`` ends the run.  Requests
still decoding then are cut by the window, not failed.

Every latency is timed from when the request was due.  The engine's idle
fast-forward must not fire inside the window: the run counts it and is
not correct if it does.  Once the engine and its pool are freed, the
plain reference (``configs/<config>.ref.py``) runs each sampled request's
prompt and served tokens, and the widest gap by which a served token's
logit lies below the reference's best is compared with its limit.
"""
from __future__ import annotations

import gc
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import BENCH_DIR
from chipbench import common as cm
from chipbench import trace as tr
from chipbench.traffic import rng, serve_schedule

FF_TOLERANCE_S = 1e-3   # engine clock drifting this far ahead = fast-forward


class Program:
    """The engine and its weights, built once per process."""

    def __init__(self, cell, seed: int, instrument: bool = False):
        from repro.models import build_model
        from repro.serve import ContinuousEngine
        from repro.serve.scheduler import FCFSScheduler

        t, cfg, ref = cell.traffic, cell.config, cell.reference
        self.cell = cell
        self.model = build_model(cm.program_config(cfg, t))
        self.make_params = jax.jit(lambda kd: ref.init_params(
            cfg, jax.random.wrap_key_data(kd), jnp.bfloat16))
        cm.check_layout(jax.eval_shape(self.make_params, cm.key_data(0)),
                        self.model.abstract_params())
        params = self.make_params(cm.key_data(seed))
        self.engine = ContinuousEngine(
            self.model, params, n_slots=t["slots"], max_len=t["max_len"],
            seed=0, scheduler=FCFSScheduler(t["max_prefills_per_step"]))
        # traced runs name the host's turns by wrapping two private
        # methods of the engine (PERF.md, Open questions)
        self.span_calls = {}
        if instrument:
            self._span("_step", "bench:decode")
            self._span("_admit_one", "bench:admit")

    def _span(self, method: str, name: str) -> None:
        inner = cm.seam(self.engine, method)
        self.span_calls[method] = 0

        def wrapped(*a, **k):
            self.span_calls[method] += 1
            with jax.profiler.TraceAnnotation(name):
                return inner(*a, **k)

        setattr(self.engine, method, wrapped)

    def reseed(self, seed: int) -> None:
        self.engine.params = None
        gc.collect()
        self.engine.params = self.make_params(cm.key_data(seed))

    def warm_up(self) -> None:
        """One request per allowed prompt length, two tokens each."""
        from repro.serve.scheduler import ServeRequest

        lens = self.cell.traffic["prompt"]["snap"]
        self.engine.generate([ServeRequest(np.zeros(n, np.int32),
                                           max_new_tokens=2) for n in lens])


def run_window(program: Program, seed: int, seconds: float,
               traced: Optional[cm.Traced] = None,
               on_window_start: Optional[Callable] = None) -> dict:
    """Drive one schedule through ``generate``; returns the raw record."""
    from repro.serve.scheduler import ServeRequest

    cell, engine = program.cell, program.engine
    t = cell.traffic
    w0 = t["ramp_s"]
    w1 = w0 + seconds
    arrivals = serve_schedule(t, cell.config, seed, seconds)
    reqs = [ServeRequest(a.prompt, max_new_tokens=a.max_new_tokens,
                         temperature=0.0, arrival_s=a.arrival_s)
            for a in arrivals]
    for r in reqs:
        engine.submit(r)
    in_window = {r.rid for r, a in zip(reqs, arrivals) if a.phase == 1}
    waiting = set(in_window)
    times: Dict[int, List[float]] = {r.rid: [] for r in reqs}
    # the engine's clock is wall time plus its idle fast-forward offset;
    # first tokens carry its stamp, which keeps ``drift`` up to date (an
    # idle jump is always followed by an admission, so by a first token)
    state = {"ff": 0, "drift": 0.0, "started": False, "trace_on": False}
    trace_end = w0 + t["trace_seconds"]
    if traced is not None:
        # the profiler starts and stops outside the engine's loop, where its
        # own pauses delay no request; the traced span opens at the window
        traced.start(span=False)
    t_gen = cm.now()

    def clock() -> float:
        return cm.now() - t_gen + state["drift"]

    def on_token(req, tok):
        if len(req.out_tokens) == 1:
            waiting.discard(req.rid)
            drift = req.first_token_s - (cm.now() - t_gen)
            if w0 <= req.first_token_s < w1 \
                    and drift - state["drift"] > FF_TOLERANCE_S:
                state["ff"] += 1
            state["drift"] = max(state["drift"], drift)
            times[req.rid].append(req.first_token_s)
        else:
            times[req.rid].append(clock())

    def should_drain():
        now = clock()
        if not state["started"] and now >= w0:
            state["started"] = True
            if on_window_start is not None:
                on_window_start()
            if traced is not None:
                traced.enter()
                state["trace_on"] = True
        if state["trace_on"] and now >= trace_end:
            traced.exit()
            state["trace_on"] = False
        return now >= w1 and not waiting

    engine.generate(on_token=on_token, should_drain=should_drain,
                    drain_grace_s=0.0)
    if traced is not None:
        traced.stop()
    return {"reqs": reqs, "times": times, "in_window": in_window,
            "w0": w0, "w1": w1, "fast_forwards": state["ff"],
            "t_gen": t_gen}


def window_stats(rec: dict, seconds: float) -> dict:
    """End-to-end numbers and counters of one window, from its record;
    ``served`` lists (prompt length, index in the output) of every token
    emitted in the window, from which ``serve_mfu`` counts operations."""
    from repro.serve.scheduler import RequestStatus

    w0, w1 = rec["w0"], rec["w1"]
    reqs = {r.rid: r for r in rec["reqs"]}
    ttft, waits, failed = [], [], 0
    for rid in rec["in_window"]:
        r = reqs[rid]
        ok_status = r.status is RequestStatus.COMPLETED or (
            r.status is RequestStatus.SHED and r.shed_reason == "drain")
        if not ok_status or not math.isfinite(r.first_token_s):
            failed += 1
            continue
        ttft.append(r.first_token_s - r.arrival_s)
        waits.append(r.admitted_s - r.arrival_s)
    # first tokens, each the end of an admission: a gap between two tokens
    # of one request spans the decode step and the admissions between them
    firsts = np.sort([ts[0] for ts in rec["times"].values() if ts])
    gaps, gap_admits, served = [], [], []
    for rid, ts in rec["times"].items():
        plen = len(reqs[rid].prompt)
        for j, x in enumerate(ts):
            if not (w0 <= x < w1):
                continue
            served.append((plen, j))
            if j:
                gaps.append(x - ts[j - 1])
                gap_admits.append(int(np.searchsorted(firsts, x)
                                      - np.searchsorted(firsts, ts[j - 1],
                                                        side="right")))
    return {
        "attempted": len(rec["in_window"]), "failed": failed,
        "ttft_s": ttft, "itl_s": gaps, "itl_admits": gap_admits,
        "queue_wait_s": waits,
        "emitted": len(served), "served": served, "seconds": seconds,
        "fast_forwards": rec["fast_forwards"],
    }


def tails(stats: dict) -> str:
    """The tails' make-up, for the log: TTFT and queue wait quantiles, and
    the gaps between tokens grouped by how many admissions ran in them."""
    q = (50, 90, 95, 99)

    def qs(xs):
        return "/".join(f"{1e3 * cm.quantile(xs, p):.1f}" for p in q)

    gaps = np.asarray(stats["itl_s"])
    admits = np.minimum(np.asarray(stats["itl_admits"], int), 4)
    groups = []
    for k in range(5):
        sel = gaps[admits == k]
        if len(sel):
            groups.append(f"{k}{'+' * (k == 4)}: {len(sel) / len(gaps):.1%} "
                          f"median {1e3 * float(np.median(sel)):.1f}")
    return (f"tails (p50/p90/p95/p99 ms): ttft {qs(stats['ttft_s'])}, queue "
            f"wait {qs(stats['queue_wait_s'])}, gap {qs(stats['itl_s'])}; "
            f"gaps by admissions in them: {'; '.join(groups)}")


def check_sample(rec: dict, seed: int, want_tokens: int) -> List:
    """Completed requests drawn from the seed, the longest among them,
    until ``want_tokens`` served tokens are in the sample."""
    from repro.serve.scheduler import RequestStatus

    done = [r for r in rec["reqs"] if r.status is RequestStatus.COMPLETED]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out_tokens),
                                       r.rid))
    order = [done[i] for i in rng(seed, 3).permutation(len(done))]
    sample, n = [longest], len(longest.out_tokens)
    for r in order:
        if n >= want_tokens:
            break
        if r is not longest:
            sample.append(r)
            n += len(r.out_tokens)
    return sample


def served_gaps(cell, seed: int, sample, mm=None, lower=None) -> dict:
    """Against the plain reference in float32: for every served token, how
    far its logit lies below the reference's best at that position.  With
    ``lower`` (a matrix product in lower precision) the token compared is
    the one that precision puts first, not the served one."""
    ref, cfg, t = cell.reference, cell.config, cell.traffic
    bucket = t["reference_bucket"]
    params = jax.jit(lambda kd: ref.init_params(
        cfg, jax.random.wrap_key_data(kd), jnp.bfloat16))(cm.key_data(seed))
    fwd = jax.jit(lambda p, x: ref.logits(p, x, cfg))
    low = (jax.jit(lambda p, x: ref.logits(p, x, cfg, lower))
           if lower is not None else None)
    widest, n = 0.0, 0
    for r in sample:
        out = np.asarray(r.out_tokens, np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), out[:-1]])
        padded = np.zeros(-(-len(seq) // bucket) * bucket, np.int32)
        padded[:len(seq)] = seq
        rows = slice(len(r.prompt) - 1, len(seq))
        logits = np.asarray(fwd(params, jnp.asarray(padded[None]))[0][rows])
        pick = out
        if low is not None:
            pick = np.asarray(low(params, jnp.asarray(padded[None]))[0][rows]
                              ).argmax(-1)
        gap = logits.max(-1) - logits[np.arange(len(out)), pick]
        widest = max(widest, float(gap.max()))
        n += len(out)
    return {"served_logit_gap": widest, "tokens_compared": n}


def counters_for(stats: dict) -> dict:
    return {"queue_wait_s": stats["queue_wait_s"], "served": stats["served"],
            "seconds": stats["seconds"]}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, token_hook: Optional[Callable] = None) -> dict:
    t = cell.traffic
    program = Program(cell, seed, instrument=trace)
    if token_hook is not None:
        token_hook(program.engine)
    program.warm_up()
    compiles = cm.CompileCounter()
    collector = cm.GcWatch()
    watch = cm.HostWatch()
    n_at_start = {}
    traced = cm.Traced(BENCH_DIR / ".traces" / cell.name) if trace else None
    rec = run_window(program, seed, seconds, traced,
                     on_window_start=lambda: n_at_start.update(
                         t=cm.now(), compiles=compiles.n))
    setup_s = n_at_start["t"] - t_start
    watch.close()
    collections = collector.close()
    if trace and not all(program.span_calls.values()):
        raise RuntimeError(f"the engine no longer calls the methods the "
                           f"traced spans wrap: {program.span_calls}")
    stats = window_stats(rec, seconds)
    device = cm.device_info(devices[:cell.chips])
    late = max([0.0] + [rec["times"][rid][0] - rec["w1"]
                        for rid in rec["in_window"] if rec["times"][rid]])
    stamps = sorted({rec["t_gen"] + x for ts in rec["times"].values()
                     for x in ts
                     if rec["w0"] <= x < rec["w1"]})
    cm.log(f"window: {stats['attempted']} arrivals, {stats['failed']} failed, "
           f"{stats['emitted']} tokens; set-up {setup_s:.3f} s; compilations "
           f"in the window {compiles.n - n_at_start['compiles']}; idle "
           f"fast-forwards in the window {stats['fast_forwards']}; last first "
           f"token {late:.3f} s after the window; peak HBM "
           f"{device['memory_peak_bytes']}; "
           f"{cm.longest_pause(stamps, rec['t_gen'] + rec['w0'], watch)}; "
           f"{collections}")
    cm.log(tails(stats))

    metrics_out, breakdown = {}, None
    if trace:
        trace_ = traced.trace
        device["busy_s"], device["window_s"] = tr.busy_s(trace_), \
            tr.window_s(trace_)
        ctx = cm.Ctx(cell, trace_, counters_for(stats),
                     devices[0].device_kind)
        metrics_out = cm.read_per_layer(cell, ctx)
        breakdown = {"device_ops": tr.top_ops(trace_),
                     "idle_gaps": tr.idle_gaps(trace_)}
        traced.cleanup()
    else:
        e2e = {
            "serve_ttft_p95_ms": 1e3 * cm.quantile(stats["ttft_s"], 95),
            "serve_itl_p95_ms": 1e3 * cm.quantile(stats["itl_s"], 95),
            "serve_tokens_per_s": stats["emitted"] / seconds,
            "setup_s": setup_s,
        }
        for m in cell.metrics("end_to_end"):
            metrics_out[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    sample = check_sample(rec, seed, t["check_tokens"])
    # free the engine, its pool and weights before the reference runs
    program.engine.pool = None
    program.engine.params = None
    del program
    gc.collect()
    read = served_gaps(cell, seed, sample)
    cm.log(f"check: {len(sample)} requests, {read['tokens_compared']} "
           "served tokens against the reference")
    ok, checks = cm.judge({"served_logit_gap": read["served_logit_gap"]},
                          cell.limits)
    ok = ok and stats["failed"] == 0 and stats["fast_forwards"] == 0 \
        and read["tokens_compared"] > 0
    return dict(correct=ok, attempted=stats["attempted"],
                failed=stats["failed"], metrics=metrics_out, device=device,
                checks=checks, breakdown=breakdown)
