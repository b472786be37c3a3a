from repro.kernels.flash_attention import FlashSpec, flash_attention
from repro.kernels.fused_ce import CESpec, fused_ce, resolve_ce_backend
from repro.kernels.lamb_update import lamb_update
from repro.kernels.ops import (
    FusedLambState,
    flash_sdpa,
    fused_lamb,
    fused_lamb_apply,
    fused_lamb_init,
    kernel_calls,
    make_fused_lamb_step,
    pallas_spec_ok,
    resolve_flash_backend,
    resolve_fused_backend,
)

__all__ = [
    "CESpec",
    "FlashSpec",
    "FusedLambState",
    "flash_attention",
    "flash_sdpa",
    "fused_ce",
    "fused_lamb",
    "fused_lamb_apply",
    "fused_lamb_init",
    "kernel_calls",
    "lamb_update",
    "make_fused_lamb_step",
    "pallas_spec_ok",
    "resolve_ce_backend",
    "resolve_flash_backend",
    "resolve_fused_backend",
]
