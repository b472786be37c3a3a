"""Operations and bytes of the kernels, counted from shapes, and the
roofline they are held to.  These are the yardstick of every roofline
share the benchmark reports; a count that runs high would let a share pass
100%, so each counts only the work the algorithm needs.  A multiply-add is
two operations.  A model's own counts (``train_flops_per_token``,
``prefill_flops``, ``decode_token_flops``) live with its configuration's
reference, ``configs/<name>.ref.py``."""
from __future__ import annotations

from typing import Tuple


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

FLASH_MATMULS = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}
FLASH_TENSORS = {  # (bf16 tensors of (B, H, S, Dh) read or written,
    #                 fp32 per-row vectors of (B, H, S))
    "flash_fwd": (4, 1),   # q, k, v -> o; logsumexp
    "flash_dq": (5, 2),    # q, k, v, do -> dq; logsumexp, di
    "flash_dkv": (6, 2),   # q, k, v, do -> dk, dv; logsumexp, di
}


def flash_call(kernel: str, b: int, h: int, s: int, dh: int,
               itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one call of a bidirectional flash-attention
    kernel over b*h heads of length s: each (S, S, Dh) product it runs
    (the backward kernels recompute the scores, as the algorithm does),
    and each tensor read or written once."""
    flops = FLASH_MATMULS[kernel] * 2.0 * b * h * s * s * dh
    big, rows = FLASH_TENSORS[kernel]
    nbytes = big * b * h * s * dh * itemsize + rows * b * h * s * 4
    return flops, nbytes


LAMB_BYTES_PER_PARAM = 7 * 4  # read w, g, m, v; write w, m, v (float32)


def lamb_bytes(n_params: int) -> float:
    """Least bytes of one fused LAMB update over ``n_params`` weights."""
    return float(LAMB_BYTES_PER_PARAM * n_params)


def n_params(shapes) -> int:
    total = 0
    for shape in shapes:
        k = 1
        for x in shape:
            k *= x
        total += k
    return total
