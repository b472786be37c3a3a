"""Median over the engine's admissions (``serve.admit`` spans wholly in
the traced window) of the span's length less the device's busy time
inside it: the host's share of an admission (chipbench.program_trace)."""
from chipbench import program_trace as pt


def read(ctx):
    return pt.admit_idle_ms(pt.load(ctx))
