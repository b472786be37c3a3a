"""Median over consecutive decode programs (``jit_step``) of the device's
idle time between them that no admission (``serve.admit``) covers: the
engine loop's own host turn (chipbench.program_trace)."""
from chipbench import program_trace as pt


def read(ctx):
    return pt.turn_host_ms(pt.load(ctx))
