"""Admissions (``serve.admit`` spans, one prefill each) over decode steps
(``serve.decode`` spans) that start in the traced window
(chipbench.program_trace)."""
from chipbench import program_trace as pt


def read(ctx):
    return pt.prefills_per_step(pt.load(ctx))
