"""Operation and byte counts against hand counts.  A model's counts are
its configuration reference's (``configs/<name>.ref.py``); the kernels'
are ``chipbench/counts.py``'s."""
from __future__ import annotations

import hashlib
import json

import pytest

from chipbench import BENCH_DIR, counts
from chipbench.spec import load_module

BERT = json.loads((BENCH_DIR / "configs/bert-large.json").read_text())
SMOL = json.loads((BENCH_DIR / "configs/smollm-360m.json").read_text())
BERT_REF = load_module(BENCH_DIR / "configs/bert-large.ref.py", "ref_bert")
SMOL_REF = load_module(BENCH_DIR / "configs/smollm-360m.ref.py", "ref_smol")
V5E = json.loads((BENCH_DIR / "peaks.json").read_text())["TPU v5 lite"]


def test_bert_large_training_flops_per_token():
    # per layer 4*1024^2 attention weights + 2*1024*4096 MLP weights
    weights = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)       # 301,989,888
    attention = 24 * 2 * 128 * 1024                           # 6,291,456
    head = 1024 * 30522 * 19 / 128                            # 4,639,344
    want = 6 * (weights + attention + head)
    got = BERT_REF.train_flops_per_token(BERT, 128, 19)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(1.878e9, rel=1e-3)


# the counts as the three cells read them before they moved to the
# references: training per token at (seq, predicted rows) bit for bit, and
# a digest of the exact values at every prompt length the serving mix
# sends and every context a decoded token of it attends to
BEFORE_MOVE = {
    "train128": "0x1.bfa2fa8000000p+30",
    "train512": "0x1.dab9556000000p+30",
    "prefill": "71f1f8f3fe716b55bc99d10f98522887907a1f3b7388e1b5896ae679d8151f8c",
    "decode": "9d923c60f6e6a3794bd6e635f0e9ef1459460cc8535425ca23f223a1915e2edd",
}


def _digest(values) -> str:
    return hashlib.sha256(" ".join(v.hex() for v in values).encode()
                          ).hexdigest()


def test_model_counts_read_what_they_read_before_the_move():
    steady = json.loads((BENCH_DIR / "traffic/serve.steady.json").read_text())
    snap = steady["prompt"]["snap"]
    contexts = range(min(snap) + 1, max(snap) + steady["output"]["max"])
    assert BERT_REF.train_flops_per_token(BERT, 128, 19).hex() == \
        BEFORE_MOVE["train128"]
    assert BERT_REF.train_flops_per_token(BERT, 512, 77).hex() == \
        BEFORE_MOVE["train512"]
    assert _digest([SMOL_REF.prefill_flops(SMOL, s) for s in snap]) == \
        BEFORE_MOVE["prefill"]
    assert _digest([SMOL_REF.decode_token_flops(SMOL, c)
                    for c in contexts]) == BEFORE_MOVE["decode"]


@pytest.mark.parametrize("kernel,matmuls,big,rows", [
    ("flash_fwd", 2, 4, 1), ("flash_dq", 3, 5, 2), ("flash_dkv", 4, 6, 2)])
def test_flash_call_counts(kernel, matmuls, big, rows):
    b, h, s, dh = 32, 16, 128, 64
    flops, nbytes = counts.flash_call(kernel, b, h, s, dh)
    assert flops == matmuls * 2 * b * h * s * s * dh
    assert nbytes == big * b * h * s * dh * 2 + rows * b * h * s * 4


def test_flash_forward_at_seq_128_is_memory_bound():
    flops, nbytes = counts.flash_call("flash_fwd", 32, 16, 128, 64)
    # 1.07 GFLOP / 197 TFLOP/s = 5.4 us; 17.0 MB / 819 GB/s = 20.7 us
    assert counts.roofline_s(flops, nbytes, V5E) == pytest.approx(
        nbytes / 819e9)


def test_lamb_bytes_are_seven_fp32_passes():
    assert counts.lamb_bytes(1000) == 28000
    assert counts.n_params([(24, 1024, 4096), (30522, 1024)]) == \
        24 * 1024 * 4096 + 30522 * 1024


def test_smollm_decode_token_flops():
    d, n, ff, v = 960, 32, 2560, 49152
    # q and o: 960x960 each; k and v: 960x320 each (5 of 15 heads); MLP 3x
    layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    ctx = 100
    want = 2 * (n * layer + n * 2 * d * ctx + d * v)
    assert SMOL_REF.decode_token_flops(SMOL, ctx) == want


def test_smollm_prefill_counts_the_last_logits_only():
    s = 128
    layer_macs = SMOL_REF.decoder_layer_macs(SMOL)
    want = 2 * (s * layer_macs + 32 * 2 * 960 * s * (s + 1) / 2 + 960 * 49152)
    assert SMOL_REF.prefill_flops(SMOL, s) == pytest.approx(want)
    # a prefill of one token is one decoded token at context 1
    assert SMOL_REF.prefill_flops(SMOL, 1) == \
        SMOL_REF.decode_token_flops(SMOL, 1)


def test_smollm_next_token_training_flops_per_token():
    # weights 32 layers as in decoding; causal attention over (S + 1) / 2
    # keys on average; the tied head on S - 1 of S positions
    seq, n_pred = 128, 127
    layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    want = 6 * (32 * layer + 32 * 2 * 960 * (seq + 1) / 2
                + 960 * 49152 * n_pred / seq)
    assert SMOL_REF.train_flops_per_token(SMOL, seq, n_pred) == \
        pytest.approx(want, rel=1e-12)


def test_control_rounds_as_float8_e4m3():
    import jax.numpy as jnp
    import numpy as np

    from chipbench.control import round_e4m3, to_fp8

    x = np.random.default_rng(0).standard_normal(20000).astype(np.float32)
    x = np.clip(x * 60, -448, 448)
    want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))
    assert (np.asarray(round_e4m3(jnp.asarray(x))) == want).all()
    # per-tensor scale: the largest magnitude maps to 448 and back exactly
    y = np.asarray(to_fp8(jnp.asarray(x * 1e-3)))
    assert np.max(np.abs(y)) == pytest.approx(np.max(np.abs(x * 1e-3)))
    assert np.max(np.abs(y - x * 1e-3) / np.max(np.abs(x * 1e-3))) < 2 ** -4
