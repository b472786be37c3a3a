"""Device ms a training step spends in the optimizer: ops under the
``optimizer`` scope (everything after the gradients) and the LAMB kernels
(``lamb_moments``, ``lamb_apply``), per ``jit_step_fn`` in the window,
mean over chips (chipbench.program_trace)."""
from chipbench import program_trace as pt


def read(ctx):
    parts = pt.step_parts_ms(pt.load(ctx))
    return parts["optimizer"] if parts else None
