"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached.  That finds what
interpret mode cannot: blocks that break the (8, 128) tiling rule, scoped
VMEM overruns, and kernels GSPMD cannot partition.  Shapes are bert-large's
(16 heads of 64, d=1024, vocab 30522, 24 layers) in bf16.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.  With ``--dist loadfile`` the worker that gets this
file is the only one that loads it.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data import make_batch
from repro.kernels import (
    flash_attention,
    flash_sdpa,
    fused_ce,
    fused_lamb_apply,
    kernel_calls,
    lamb_update,
)
from repro.models import build_model
from repro.sharding import ShardCtx, use_sharding
from repro.train.loss import fused_cross_entropy
from repro.train.step import make_train_step

HEADS, HEAD_DIM, D_MODEL, VOCAB, LAYERS = 16, 64, 1024, 30522, 24
BATCH = 8
MLM_ROWS = 32 * math.ceil(0.15 * 128)   # batch 32 × max predictions at seq 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # these compiles could be written to a persistent cache but never read
    # back without a chip: keep the cache off while they run
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its lock is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("seq", [128, 512])
def test_flash_fwd_bwd_compiles(one_chip, seq):
    """Bidirectional flash with per-example kv_valid, fwd + both bwd kernels."""
    qkv = jax.ShapeDtypeStruct((BATCH, HEADS, seq, HEAD_DIM), jnp.bfloat16,
                               sharding=one_chip)
    valid = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)

    def grads(q, k, v, valid):
        def loss(q, k, v):
            o = flash_attention(q, k, v, valid, causal=False, backend="pallas")
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(grads, qkv, qkv, qkv, valid)
    (fwd,) = kernel_calls(hlo, "flash_fwd")
    assert f"bf16[{BATCH * HEADS},{seq},{HEAD_DIM}]" in fwd
    assert f"f32[{BATCH * HEADS},1,{seq}]" in fwd   # lane-dense logsumexp
    assert kernel_calls(hlo, "flash_dq")
    assert kernel_calls(hlo, "flash_dkv")


def test_fused_ce_fwd_bwd_compiles(one_chip):
    """The MLM head's rows at seq 128 against the full bert vocab."""
    h = jax.ShapeDtypeStruct((MLM_ROWS, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((VOCAB, D_MODEL), jnp.bfloat16, sharding=one_chip)
    lbl = jax.ShapeDtypeStruct((MLM_ROWS,), jnp.int32, sharding=one_chip)

    def grads(h, w, lbl):
        def loss(h, w):
            nll, _ = fused_ce(h, w, lbl, backend="pallas")
            return jnp.sum(nll)
        return jax.grad(loss, argnums=(0, 1))(h, w)

    hlo = _compile(grads, h, w, lbl)
    (fwd,) = kernel_calls(hlo, "fused_ce_fwd")
    assert f"f32[1,{MLM_ROWS}]" in fwd
    assert kernel_calls(hlo, "fused_ce_dh")
    assert kernel_calls(hlo, "fused_ce_dw")


@pytest.mark.parametrize("shape,layer_axis,tc", [
    ((LAYERS, D_MODEL, HEADS, HEAD_DIM), 0, D_MODEL),  # stacked attention
    ((LAYERS, D_MODEL), 0, D_MODEL),                   # stacked bias
    ((VOCAB, D_MODEL), None, D_MODEL),                 # unstacked embedding
    ((2, 64, 32768), 0, 8192),      # minor dim too wide: column tiles
    ((2, 15, 64, 960), 0, 960),     # smollm's attn.wo: a 960-wide tile
    ((49152, 960), None, 8192),     # smollm's embedding, 49152 minor
], ids=["stacked", "bias", "embedding", "wide", "odd", "wide-first"])
def test_lamb_update_compiles(one_chip, shape, layer_axis, tc):
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def update(x, g, m, v, step):
        return lamb_update(x, g, m, v, step, jnp.float32(1e-3),
                           layer_axis=layer_axis)

    hlo = _compile(update, leaf, leaf, leaf, leaf, step)
    layers = shape[0] if layer_axis == 0 else 1
    (moments,) = kernel_calls(hlo, "lamb_moments")
    assert f"f32[{layers},8,{tc}]" in moments   # per-layer partial sums
    assert kernel_calls(hlo, "lamb_apply")


RELAYOUT = re.compile(r"copy|pad|slice|reshape|transpose")


def _weight_sized_ops(hlo: str, scope=None, floor: int = 1 << 20) -> list:
    """Ops of the entry computation with an array of ``floor`` or more
    elements, other than the LAMB kernels and ops that move no data.  With
    ``scope``, only those under that named scope whose op or fusion is a
    relayout (copy, pad, slice, reshape or transpose)."""
    entry = re.search(r"^ENTRY .*?^}", hlo, re.S | re.M).group(0)
    found = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if not m or m.group(3) in ("parameter", "get-tuple-element",
                                   "bitcast", "tuple", "constant"):
            continue
        if "/lamb_moments/" in line or "/lamb_apply/" in line:
            continue
        if scope is not None and (scope not in line
                                  or not RELAYOUT.search(m.group(1))):
            continue
        sizes = [math.prod(int(d) for d in dims.split(",") if d)
                 for dims in re.findall(r"\[([\d,]*)\]", m.group(2))]
        if max(sizes, default=0) >= floor:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("arch,n_leaves", [
    ("bert-large", 13),
    ("smollm-360m", 11),   # 960 wide: no dim of wq, wk, wv, wo is 128-aligned
])
def test_fused_lamb_apply_in_place_compiles(one_chip, arch, n_leaves):
    """A model's leaves through the fused apply, params, m and v donated
    as the train step donates its state: every weight-sized op is a LAMB
    kernel.  No relayout of a leaf to the kernels' view or back (copy,
    reshape or transpose fusion), no pad or slice of a ragged leaf, no
    copy of a donated input the kernels write over."""
    model = build_model(get_config(arch))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, jnp.float32, sharding=one_chip), shapes)
    assert len(jax.tree.leaves(leaves)) == n_leaves
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)  # noqa: E731

    def apply(p, g, m, v, count, lr):
        return fused_lamb_apply(
            p, g, m, v, count, lr, wd_mask=model.wd_mask(),
            trust_mask=model.trust_mask(), layer_axes=model.layer_axes(),
            mode="pallas")

    compiled = jax.jit(apply, donate_argnums=(0, 2, 3)).lower(
        leaves, leaves, leaves, leaves, scalar(jnp.int32),
        scalar(jnp.float32)).compile()
    hlo = compiled.as_text()
    assert len(kernel_calls(hlo, "lamb_apply")) == n_leaves
    assert _weight_sized_ops(hlo) == []
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20) * 4


def test_train_step_optimizer_relayouts_no_leaf(one_chip):
    """bert-large's fused-LAMB train step, state donated as the Trainer
    donates it, at 2 layers and batch 4 × 128 (the optimizer's ops depend
    on neither): under the ``optimizer`` scope no weight-sized copy, pad,
    slice, reshape or transpose is left, only clipping's multiplies and
    the kernels."""
    cfg = dataclasses.replace(get_config("bert-large"), n_layers=2)
    model = build_model(cfg)
    tc = TrainConfig(optimizer="lamb", use_fused_lamb=True,
                     fused_backend="pallas", precision="bf16",
                     grad_clip_norm=1.0)
    init, step = make_train_step(model, tc)
    place = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        np.shape(a), a.dtype, sharding=one_chip)
    state = jax.tree.map(place, jax.eval_shape(init, jax.random.key(0)))
    batch = jax.tree.map(lambda a: place(np.asarray(a)),
                         make_batch(cfg, np.random.default_rng(0), 4, 128))
    hlo = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch).compile().as_text()
    assert len(kernel_calls(hlo, "lamb_apply")) == 13
    assert _weight_sized_ops(hlo, scope="/optimizer/") == []


def test_flash_sharded_data4_runs_per_chip_batch(mesh4):
    """Under a data=4 mesh the kernels run per shard (shard_map): each chip's
    flash call sees its quarter of the batch, not the global one."""
    b, s = 32, 128
    rows = NamedSharding(mesh4, P("data"))
    qkv = jax.ShapeDtypeStruct((b, s, HEADS, HEAD_DIM), jnp.bfloat16,
                               sharding=rows)
    valid = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=rows)

    def grads(q, k, v, valid):
        def loss(q, k, v):
            o = flash_sdpa(q, k, v, causal=False, kv_valid=valid,
                           backend="pallas")
            return jnp.sum(o.astype(jnp.float32))
        with use_sharding(ShardCtx(mesh4)):
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(grads, qkv, qkv, qkv, valid)
    (fwd,) = kernel_calls(hlo, "flash_fwd")
    assert f"bf16[{b // 4 * HEADS},{s},{HEAD_DIM}]" in fwd, fwd
    assert kernel_calls(hlo, "flash_dkv")


def test_fused_ce_sharded_data4_runs_per_chip_rows(mesh4):
    """The MLM head under data=4: each chip's CE kernels see its own rows
    against a replicated vocab projection."""
    b, s, p = 32, 128, math.ceil(0.15 * 128)
    rows = NamedSharding(mesh4, P("data"))
    hidden = jax.ShapeDtypeStruct((b, s, D_MODEL), jnp.bfloat16, sharding=rows)
    labels = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rows)
    w = jax.ShapeDtypeStruct((VOCAB, D_MODEL), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, P(None, "data")))

    def grads(hidden, labels, w):
        def loss(hidden, w):
            ce, _ = fused_cross_entropy(hidden, labels, w, max_positions=p,
                                        backend="pallas")
            return ce
        with use_sharding(ShardCtx(mesh4)):
            return jax.grad(loss, argnums=(0, 1))(hidden, w)

    hlo = _compile(grads, hidden, labels, w)
    (dh,) = kernel_calls(hlo, "fused_ce_dh")
    per_chip = b // 4 * p              # 160 rows, padded to two 128-row blocks
    assert f"bf16[{-(-per_chip // 128) * 128},{D_MODEL}]" in dh, dh
    assert kernel_calls(hlo, "fused_ce_fwd")
    assert kernel_calls(hlo, "fused_ce_dw")
