"""Median wait from a window request's due time to its admission, from
the scheduler's own stamps (host clock)."""
import numpy as np


def read(ctx):
    waits = ctx.counters.get("queue_wait_s")
    return 1e3 * float(np.median(waits)) if waits else None
