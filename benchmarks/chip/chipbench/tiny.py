"""A tiny copy of the benchmark tree, for the harness's tests on the CPU.

``build(root)`` copies ``benchmarks/chip`` under ``root`` and adds, as new
files and new ``BENCHMARK.json`` entries only, tiny configurations at
widths a test can hold: an encoder trained under MLM, a decoder served and
the same decoder trained next-token (with float32 masters, under the
decoder's reference), and a mixture-of-experts configuration with latent
attention and a leading dense layer, which the program builds from its
``program`` block alone (its reference is left to the change that brings
such a model).  It adds their traffic mixes, limits and cells, and a peaks
entry for the CPU.  Nothing that was copied is edited: this is also how a
later change adds a cell.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import BENCH_DIR, REPO

TRAIN, TRAIN4, SERVE = "tiny-bert.train", "tiny-bert.train4", "tiny-lm.serve"
LM_TRAIN = "tiny-lm.train"
MOE = "tiny-moe"

# limits of the tiny cells' checks, between the program's readings in
# bfloat16 on the CPU and the float8 control's at the same size
TINY_LIMITS = {
    TRAIN: {"grad_norm_gap": 1e-2, "change_norm_gap": 1.5e-2},
    TRAIN4: {"grad_norm_gap": 1e-2, "change_norm_gap": 1.5e-2},
    SERVE: {"served_logit_gap": 1e-2},
    LM_TRAIN: {"grad_norm_gap": 3e-3, "change_norm_gap": 1.5e-2},
}


def _add_json(path: Path, obj) -> None:
    if path.exists():
        raise FileExistsError(f"{path} exists: a new cell only adds files")
    path.write_text(json.dumps(obj, indent=1))


def build(root: Path) -> Path:
    """The copied tree's ``benchmarks/chip`` directory."""
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", ".traces", "test_*", "testdata"))
    top = json.loads((REPO / "BENCHMARK.json").read_text())

    enc = json.loads((bench / "configs/bert-large.json").read_text())
    enc.update(name="tiny-bert", hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=128, vocab_size=512)
    dec = json.loads((bench / "configs/smollm-360m.json").read_text())
    dec.update(name="tiny-lm", hidden_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=256, vocab_size=4096,
               max_position_embeddings=256)
    dec32 = dict(dec, name="tiny-lm-f32",
                 program=dict(dec["program"], param_dtype="float32"))
    moe = dict(dec, name=MOE, num_hidden_layers=3, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=4,
               intermediate_size=128, vocab_size=512,
               program=dict(dec["program"], family="moe", n_experts=8,
                            n_experts_per_tok=2, n_shared_experts=1,
                            moe_d_ff=32, n_dense_layers=1, use_mla=True,
                            q_lora_rank=32, kv_lora_rank=16,
                            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16))
    _add_json(bench / f"configs/{MOE}.json", moe)
    for cfg, ref in ((enc, "bert-large"), (dec, "smollm-360m"),
                     (dec32, "smollm-360m")):
        _add_json(bench / f"configs/{cfg['name']}.json", cfg)
        shutil.copy(bench / f"configs/{ref}.ref.py",
                    bench / f"configs/{cfg['name']}.ref.py")

    train = json.loads((bench / "traffic/train.seq128.json").read_text())
    train.update(seq_len=16, global_batch=4, max_predictions=3,
                 first_token_id=10, mask_token_id=3, reference_rows=2,
                 trace_steps=2)
    _add_json(bench / "traffic/tiny.train.json", train)
    _add_json(bench / "traffic/tiny.train4.json",
              dict(train, global_batch=8, mesh="data=4"))
    lm = {k: v for k, v in train.items()
          if k not in ("mask_prob", "max_predictions", "mask_token_id")}
    lm.update(objective="causal_lm",
              what="next-token rows of 16 tokens, each an unbroken stream")
    _add_json(bench / "traffic/tiny.lm.json", lm)
    serve = json.loads((bench / "traffic/serve.steady.json").read_text())
    serve.update(slots=4, max_len=128, rate_per_s=200.0, ramp_s=0.5,
                 tail_s=0.5, trace_seconds=0.2, check_tokens=200,
                 reference_bucket=32,
                 prompt={"median": 8, "sigma": 1.0, "min": 4, "max": 32,
                         "snap": [4, 8, 16, 32]},
                 output={"median": 8, "sigma": 0.8, "min": 1, "max": 16})
    _add_json(bench / "traffic/tiny.serve.json", serve)
    for name, limits in TINY_LIMITS.items():
        _add_json(bench / f"limits/{name}.json", limits)

    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="stand-in for tests")
    (bench / "peaks.json").write_text(json.dumps(peaks))

    top["configs"] += [
        {"name": "tiny-bert", "source": "test",
         "file": "benchmarks/chip/configs/tiny-bert.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-lm", "source": "test",
         "file": "benchmarks/chip/configs/tiny-lm.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-lm-f32", "source": "test",
         "file": "benchmarks/chip/configs/tiny-lm-f32.json", "reduced": [],
         "why": "test"},
        {"name": MOE, "source": "test",
         "file": f"benchmarks/chip/configs/{MOE}.json", "reduced": [],
         "why": "test"}]
    top["workloads"] += [
        {"name": TRAIN, "config": "tiny-bert", "traffic": "tiny.train",
         "chips": 1, "why": "test"},
        {"name": TRAIN4, "config": "tiny-bert", "traffic": "tiny.train4",
         "chips": 4, "why": "test"},
        {"name": SERVE, "config": "tiny-lm", "traffic": "tiny.serve",
         "chips": 1, "why": "test"},
        {"name": LM_TRAIN, "config": "tiny-lm-f32", "traffic": "tiny.lm",
         "chips": 1, "why": "test"}]
    for m in top["end_to_end"] + top["per_layer"]:
        if "workloads" not in m:
            continue
        if "bert-large.train.seq128" in m["workloads"]:
            m["workloads"] += [TRAIN, TRAIN4, LM_TRAIN]
        if any(w.startswith("smollm-360m.serve") for w in m["workloads"]):
            m["workloads"].append(SERVE)
    (root / "BENCHMARK.json").write_text(json.dumps(top, indent=1))
    return bench
