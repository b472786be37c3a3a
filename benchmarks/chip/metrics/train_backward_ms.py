"""Device ms a training step spends in the model's backward: ops under
``transpose(jvp(model))`` and the backward kernels (``flash_dq``,
``flash_dkv``, ``fused_ce_dh``, ``fused_ce_dw``), per ``jit_step_fn`` in
the window, mean over chips (chipbench.program_trace)."""
from chipbench import program_trace as pt


def read(ctx):
    parts = pt.step_parts_ms(pt.load(ctx))
    return parts["backward"] if parts else None
