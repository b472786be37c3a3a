#!/usr/bin/env python3
"""Sweep a serving cell's offered rate and admission cap once, to place
the cell's fixed rate below the knee.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \
        --points 7:4,7:2,9:4 [--seconds 20]

Each point is ``rate:cap`` (requests a second : prefills a step).  One
engine serves every point in turn, each with its own ramp and window;
per point it prints the tails of time to first token and of the gap
between tokens, tokens a second, the median queue wait and failures.
Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from chipbench.spec import Cell  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--points", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    cell = Cell(args.workload)
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: no TPU")
    from chipbench import serve as S

    run.enable_compile_cache()
    prog = S.Program(cell, args.seed)
    prog.warm_up()
    for point in args.points.split(","):
        rate, cap = point.split(":")
        cell.traffic["rate_per_s"] = float(rate)
        prog.engine.scheduler.max_prefills_per_step = int(cap)
        rec = S.run_window(prog, args.seed, args.seconds)
        st = S.window_stats(rec, args.seconds)
        q = lambda xs, p: 1e3 * float(np.percentile(xs, p)) if xs else None  # noqa: E731
        print(json.dumps({
            "rate_per_s": float(rate), "max_prefills_per_step": int(cap),
            "arrivals": st["attempted"], "failed": st["failed"],
            "ttft_p50_ms": q(st["ttft_s"], 50), "ttft_p95_ms": q(st["ttft_s"], 95),
            "ttft_max_ms": q(st["ttft_s"], 100),
            "itl_p50_ms": q(st["itl_s"], 50), "itl_p95_ms": q(st["itl_s"], 95),
            "tokens_per_s": st["emitted"] / args.seconds,
            "queue_wait_p50_ms": q(st["queue_wait_s"], 50),
            "queue_wait_p95_ms": q(st["queue_wait_s"], 95),
            "fast_forwards": st["fast_forwards"]}), flush=True)


if __name__ == "__main__":
    main()
