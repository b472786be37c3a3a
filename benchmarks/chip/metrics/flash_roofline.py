"""Flash attention's kernels (flash_fwd, flash_dq, flash_dkv) against
their roofline: the least time of each call, from its shapes
(counts.flash_call), over the device time of its events in the trace."""
from chipbench import counts, trace

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    c = ctx.counters
    least = spent = 0.0
    for k in KERNELS:
        events = trace.kernel_events(ctx.trace, k)
        if not events:
            continue
        flops, nbytes = counts.flash_call(k, c["batch_per_chip"], c["heads"],
                                          c["seq_len"], c["head_dim"])
        least += len(events) * counts.roofline_s(flops, nbytes, ctx.peak)
        spent += sum(e[2] for e in events) / 1e9
    return 100.0 * least / spent if spent else None
