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
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    flash_attention,
    flash_sdpa,
    fused_ce,
    kernel_calls,
    lamb_update,
)
from repro.sharding import ShardCtx, use_sharding
from repro.train.loss import fused_cross_entropy

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


@pytest.mark.parametrize("shape,layer_axis", [
    ((LAYERS, D_MODEL, HEADS, HEAD_DIM), 0),   # stacked attention weight
    ((LAYERS, D_MODEL), 0),                    # stacked bias
    ((VOCAB, D_MODEL), None),                  # unstacked embedding
], ids=["stacked", "bias", "embedding"])
def test_lamb_update_compiles(one_chip, shape, layer_axis):
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def update(x, g, m, v, step):
        return lamb_update(x, g, m, v, step, jnp.float32(1e-3),
                           layer_axis=layer_axis)

    hlo = _compile(update, leaf, leaf, leaf, leaf, step)
    layers = shape[0] if layer_axis == 0 else 1
    (moments,) = kernel_calls(hlo, "lamb_moments")
    assert f"f32[{layers},8,128]" in moments   # per-layer partial sums
    assert kernel_calls(hlo, "lamb_apply")


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
