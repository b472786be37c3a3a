"""BENCHMARK.json keeps to the benchmark's contract, and a cell, a
configuration and a per-layer metric are found by name from new files
alone."""
from __future__ import annotations

import hashlib
import json
import re

import pytest

from chipbench import BENCH_DIR, REPO, tiny
from chipbench.spec import Cell

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_have_just_the_contract_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert c["file"].startswith("benchmarks/chip/")
        assert (REPO / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [x["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[s]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_four_chip_cells_are_at_most_half():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(workload):
    cell = Cell(workload)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = cell.metrics("per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], workload)
        assert callable(cell.reader(m["name"]).read)
    assert cell.limits and hasattr(cell.reference, "param_specs")


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for layer in layers:
        assert "\n" not in layer and 1 <= len(layer) <= 200


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in sorted(root.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_config_and_metric_need_only_new_files(tmp_path):
    bench = tiny.build(tmp_path)
    copied = {k: v for k, v in _digest(BENCH_DIR).items()
              if not k.startswith(("test_", "testdata"))}
    (bench / "metrics" / "tiny_probe.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    top = json.loads((tmp_path / "BENCHMARK.json").read_text())
    top["per_layer"].append({
        "name": "tiny_probe", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_tokens_per_s", "workloads": [tiny.TRAIN]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(top))
    after = _digest(bench)
    assert all(after[k] == v for k, v in copied.items()
               if k != "peaks.json"), "an existing file was edited"
    cell = Cell(tiny.TRAIN, repo=tmp_path, bench_dir=bench)
    assert cell.config["name"] == "tiny-bert"
    assert cell.traffic["seq_len"] == 16
    assert "tiny_probe" in {m["name"] for m in cell.metrics("per_layer")}
    assert cell.reader("tiny_probe").read(None) == 42.0
    assert Cell(tiny.SERVE, repo=tmp_path, bench_dir=bench).config[
        "name"] == "tiny-lm"
    # a decoder trained next-token, under the decoder's reference
    lm = Cell(tiny.LM_TRAIN, repo=tmp_path, bench_dir=bench)
    assert lm.config["program"]["param_dtype"] == "float32"
    assert lm.traffic["objective"] == "causal_lm"
    assert callable(lm.reference.nll_sum)
    assert callable(lm.reference.train_flops_per_token)
    # an architecture the program builds from its program block alone
    moe = json.loads((bench / "configs" / f"{tiny.MOE}.json").read_text())
    assert moe["program"]["use_mla"] and moe["program"]["n_experts"] == 8


# what program_config built for the cells before it took the program block
# by field name, field for field; every other field is ModelConfig's default
BERT_BEFORE = dict(
    name="bert-large", family="dense", n_layers=24, d_model=1024, n_heads=16,
    n_kv_heads=16, d_ff=4096, vocab_size=30522, head_dim=64,
    rope_theta=10000.0, causal=False, norm_type="layernorm", act_fn="gelu",
    gated_mlp=False, use_rope=True, tie_embeddings=True, mask_ratio=0.15,
    use_flash_kernel=True, use_fused_ce_head=True, param_dtype="float32",
    activation_dtype="bfloat16")
SMOL_BEFORE = dict(
    name="smollm-360m", family="dense", n_layers=32, d_model=960, n_heads=15,
    n_kv_heads=5, d_ff=2560, vocab_size=49152, head_dim=64,
    rope_theta=10000.0, causal=True, norm_type="rmsnorm", act_fn="silu",
    gated_mlp=True, use_rope=True, tie_embeddings=True, mask_ratio=0.0,
    use_flash_kernel=False, use_fused_ce_head=False,
    param_dtype="bfloat16", activation_dtype="bfloat16",
    mlm_max_predictions=None)


@pytest.mark.parametrize("workload,before", [
    ("bert-large.train.seq128", dict(BERT_BEFORE, mlm_max_predictions=20)),
    ("bert-large.train.seq512", dict(BERT_BEFORE, mlm_max_predictions=80)),
    ("bert-large.train.seq128.data4",
     dict(BERT_BEFORE, mlm_max_predictions=20)),
    ("smollm-360m.serve.steady", SMOL_BEFORE)])
def test_program_config_builds_what_it_built_before(workload, before):
    import dataclasses

    from chipbench import common as cm
    from repro.configs.base import ModelConfig

    defaults = {f.name: f.default for f in dataclasses.fields(ModelConfig)
                if f.default is not dataclasses.MISSING}
    cell = Cell(workload)
    got = dataclasses.asdict(cm.program_config(cell.config, cell.traffic))
    assert got == {**defaults, **before}


def test_an_unknown_program_key_is_named():
    from chipbench import common as cm

    cell = Cell("smollm-360m.serve.steady")
    cfg = dict(cell.config, program=dict(cell.config["program"],
                                          n_expert=8))
    with pytest.raises(KeyError, match="n_expert"):
        cm.program_config(cfg, cell.traffic)


def test_moe_and_latent_attention_come_from_the_program_block(tmp_path):
    from chipbench import common as cm
    from repro.models import build_model

    bench = tiny.build(tmp_path)
    cfg = json.loads((bench / "configs" / f"{tiny.MOE}.json").read_text())
    lm = json.loads((bench / "traffic/tiny.lm.json").read_text())
    mc = cm.program_config(cfg, lm)
    assert (mc.family, mc.n_experts, mc.n_experts_per_tok,
            mc.n_shared_experts, mc.moe_d_ff, mc.n_dense_layers,
            mc.use_mla) == ("moe", 8, 2, 1, 32, 1, True)
    params = build_model(mc).abstract_params()
    assert "dense_blocks" in params
    # expert leaves stacked as (layers, experts, d, f)
    assert params["blocks"]["moe"]["wi"].shape == (2, 8, 64, 32)
    assert params["blocks"]["moe"]["wo"].shape == (2, 8, 32, 64)
    # latent attention: keys and values through the kv_lora_rank latent
    assert params["blocks"]["attn"]["wkv_a"].shape == (2, 64, 16 + 8)


def test_a_metric_whose_count_the_reference_lacks_stops_set_up(tmp_path):
    bench = tiny.build(tmp_path)
    ref = bench / "configs" / "tiny-lm-f32.ref.py"
    text = ref.read_text().replace("def train_flops_per_token(",
                                   "def _gone(")
    ref.write_text(text)
    with pytest.raises(AttributeError,
                       match=r"train_mfu, which needs train_flops_per_token"):
        Cell(tiny.LM_TRAIN, repo=tmp_path, bench_dir=bench)


def test_shared_lamb_reference_slices_by_leading_axes():
    import jax.numpy as jnp
    import numpy as np

    from chipbench import ref_lamb

    tree = {"blocks": {"moe": {"wi": jnp.ones((3, 8, 4, 5))},
                       "attn": {"wq": jnp.ones((3, 4, 2))}},
            "embed": jnp.ones((6, 4))}
    axes = {"blocks/moe/wi": 2, "blocks/attn/wq": 1, "embed": 0}
    norms = ref_lamb.slice_norms(tree, axes.__getitem__)
    assert norms["blocks/moe/wi"].shape == (3, 8)
    assert np.allclose(norms["blocks/moe/wi"], np.sqrt(20))
    assert norms["blocks/attn/wq"].shape == (3,)
    assert norms["embed"].shape == (1,)
    assert np.allclose(norms["embed"], np.sqrt(24))
