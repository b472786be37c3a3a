#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes, limits and per-layer metrics are
data under ``benchmarks/chip`` and entries of ``BENCHMARK.json``.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part
of the window.  The last line on stdout is one JSON object; the numbers
``correct`` compares are the last lines on stderr.  The run needs a TPU
with as many chips as the cell asks for, and fails without one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# libtpu's logs would go to a fixed directory under /tmp, shared by runs
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import REPO  # noqa: E402
from chipbench.spec import Cell  # noqa: E402

RUNNERS = {"train": "chipbench.train", "serve": "chipbench.serve"}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed directory inside the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = Cell(args.workload)
    import importlib

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < cell.chips:
        sys.exit(f"{cell.name} needs {cell.chips} chips, JAX found "
                 f"{len(devices)}")
    enable_compile_cache()
    from chipbench import common as cm

    runner = importlib.import_module(RUNNERS[cell.traffic["kind"]])
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START, devices)
    cm.emit(**result)


if __name__ == "__main__":
    main()
