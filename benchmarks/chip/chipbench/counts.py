"""Operations and bytes, counted from shapes.  These are the yardstick of
every utilisation and roofline share the benchmark reports; a count that
runs high would let a share pass 100%, so each counts only the work the
algorithm needs.  A multiply-add is two operations."""
from __future__ import annotations

from typing import Tuple


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# bert-large training
# ---------------------------------------------------------------------------

def encoder_train_flops_per_token(cfg: dict, seq: int, n_pred: int) -> float:
    """Model operations per token of one MLM training step: forward and
    backward (3 matrix products per forward one) of the encoder's weight
    products, attention's score and value products over ``seq`` keys, and
    the tied MLM head on the ``n_pred`` predicted rows of each sequence.
    Recomputation is not counted."""
    d, n, ff, v = (cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["intermediate_size"], cfg["vocab_size"])
    weights = n * (4 * d * d + 2 * d * ff)
    attention = n * 2 * seq * d
    head = d * v * n_pred / seq
    return 6.0 * (weights + attention + head)


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


# ---------------------------------------------------------------------------
# decoder serving
# ---------------------------------------------------------------------------

def decoder_layer_macs(cfg: dict) -> float:
    """Weight multiply-adds of one token through every layer."""
    d, n, h, ff = (cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["num_attention_heads"], cfg["intermediate_size"])
    kv = cfg.get("num_key_value_heads", h)
    dh = d // h
    return n * (2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff)


def decode_token_flops(cfg: dict, ctx: int) -> float:
    """Operations of one decoded token that attends to ``ctx`` positions,
    its logits included."""
    d, n, h = (cfg["hidden_size"], cfg["num_hidden_layers"],
               cfg["num_attention_heads"])
    attention = n * 2 * d * ctx          # scores and values, all heads
    return 2.0 * (decoder_layer_macs(cfg) + attention
                  + d * cfg["vocab_size"])


def prefill_flops(cfg: dict, s: int) -> float:
    """Operations of a causal prefill of ``s`` tokens with the logits of
    its last position only."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    attention = n * 2 * d * s * (s + 1) / 2
    return 2.0 * (s * decoder_layer_macs(cfg) + attention
                  + d * cfg["vocab_size"])
