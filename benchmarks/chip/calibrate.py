#!/usr/bin/env python3
"""Readings that set the limits of a cell's ``correct`` check.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,... [--control-seeds ...] [--fault-seeds ...] \
        [--seconds 8] [--out readings.json]

For every seed of ``--seeds``, the numbers the cell compares, read from
the program at the cell's own size (training: its first steps through
the step ``Trainer`` builds; serving: a short window at the cell's load).
For ``--control-seeds``, the same numbers with the reference put in the
program's place and computed in float8 (``chipbench/control.py``).  For
``--fault-seeds`` (training), the numbers of planted faults: half of the
batch left out and the mean taken over the rest, and, on a mesh, the
exchange between chips left out (each chip's quarter of the batch alone).
Everything runs in one process, so the program compiles once.  A step
that returns its state unchanged reads 1 on the weights' change and
needs no run.  Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from chipbench.spec import Cell  # noqa: E402


def seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def train_readings(cell, args) -> dict:
    from chipbench import train as T
    from chipbench.control import fp8_matmul

    out = {"program": {}, "control": {}, "faults": {}}
    prog = T.Program(cell)
    for seed in seeds(args.seeds):
        state, pipe, mine = prog.first_steps(seed)
        del state, pipe
        gc.collect()
        out["program"][seed] = T.readings(mine, T.reference_readings(cell, seed))
        print("program", seed, out["program"][seed], flush=True)
    del prog
    gc.collect()
    b = cell.traffic["global_batch"]
    for seed in seeds(args.control_seeds) + seeds(args.fault_seeds):
        ref = T.reference_readings(cell, seed)
        if seed in seeds(args.control_seeds):
            low = T.reference_readings(cell, seed, mm=fp8_matmul)
            out["control"][seed] = T.readings(low, ref)
            print("control", seed, out["control"][seed], flush=True)
        if seed in seeds(args.fault_seeds):
            half = T.reference_readings(cell, seed, rows_from=b // 2)
            out["faults"].setdefault("half_batch", {})[seed] = \
                T.readings(half, ref)
            if cell.chips > 1:
                one = T.reference_readings(cell, seed,
                                           rows_from=b - b // cell.chips)
                out["faults"].setdefault("no_exchange", {})[seed] = \
                    T.readings(one, ref)
            print("faults", seed, {k: v[seed] for k, v in
                                   out["faults"].items()}, flush=True)
    return out


def serve_readings(cell, args) -> dict:
    from chipbench import serve as S
    from chipbench.control import fp8_matmul

    out = {"program": {}, "control": {}}
    ctl = set(seeds(args.control_seeds))
    prog = None
    for seed in seeds(args.seeds) + [s for s in ctl
                                     if s not in seeds(args.seeds)]:
        if prog is None:
            prog = S.Program(cell, seed)
            prog.warm_up()
        else:
            prog.reseed(seed)
        rec = S.run_window(prog, seed, args.seconds)
        stats = S.window_stats(rec, args.seconds)
        sample = S.check_sample(rec, seed, cell.traffic["check_tokens"])
        if seed in seeds(args.seeds):
            out["program"][seed] = dict(S.served_gaps(cell, seed, sample),
                                        failed=stats["failed"],
                                        fast_forwards=stats["fast_forwards"])
            print("program", seed, out["program"][seed], flush=True)
        if seed in ctl:
            out["control"][seed] = S.served_gaps(cell, seed, sample,
                                                 lower=fp8_matmul)
            print("control", seed, out["control"][seed], flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args()
    cell = Cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: no TPU")
    run.enable_compile_cache()
    t0 = time.perf_counter()
    kind = cell.traffic["kind"]
    out = (train_readings if kind == "train" else serve_readings)(cell, args)
    out["seconds"] = time.perf_counter() - t0
    text = json.dumps(out, indent=1, default=float)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(text)
    print(text)


if __name__ == "__main__":
    main()
