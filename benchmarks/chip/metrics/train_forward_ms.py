"""Device ms a training step spends in the model's forward: ops under the
``model`` scope not under a transpose (``jvp(model)``), the forward
kernels (``flash_fwd``, ``fused_ce_fwd``) and the bf16 cast of the
weights (``cast_params``), per ``jit_step_fn`` in the window, mean over
chips (chipbench.program_trace)."""
from chipbench import program_trace as pt


def read(ctx):
    parts = pt.step_parts_ms(pt.load(ctx))
    return parts["forward"] + parts["cast"] if parts else None
