"""The chip benchmark's harness: cells, configurations, traffic mixes and
per-layer metrics are data under ``benchmarks/chip``, found by name."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]

# the system under test is imported from the checkout's src/
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))
