"""Median device time of one decode step over the slot pool (the engine's
jitted ``step``) in the traced part of the window."""
import numpy as np

from chipbench import trace


def read(ctx):
    events = trace.module_events(ctx.trace, r"^jit_step\b")
    return float(np.median([e[2] for e in events])) / 1e6 if events else None
