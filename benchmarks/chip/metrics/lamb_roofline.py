"""The fused LAMB kernels (lamb_moments, lamb_apply) against the least
bytes an update needs (counts.lamb_bytes over every weight, each traced
step), over their device time.  Only where every leaf takes the kernel:
one chip.  On a mesh the sharded leaves take the XLA form."""
from chipbench import counts, trace


def read(ctx):
    c = ctx.counters
    if c["chips"] != 1 or not c["steps_traced"]:
        return None
    events = (trace.kernel_events(ctx.trace, "lamb_moments")
              + trace.kernel_events(ctx.trace, "lamb_apply"))
    if not events:
        return None
    least = c["steps_traced"] * counts.lamb_bytes(c["n_params"]) \
        / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e[2] for e in events) / 1e9)
