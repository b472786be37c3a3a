"""Pallas kernels vs their pure-jnp oracles (interpret mode): shape/dtype
sweeps per the per-kernel test requirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, flash_sdpa, fused_lamb, lamb_update
from repro.kernels.ref import flash_attention_ref, lamb_update_ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# fused LAMB
# ---------------------------------------------------------------------------

LAMB_SHAPES = [
    ((128,), None),
    ((1000,), None),            # non-multiple of block
    ((8, 16), None),
    ((4, 300), 0),              # stacked layers, ragged per-layer size
    ((2, 64, 32), 0),
    ((1, 9000), 0),
    ((3, 4096), 0),
    # a minor dim too wide for one tile: several column tiles per layer, a
    # ragged last one; the per-layer norm sums accumulate across tiles
    ((2, 140000), 0),
    ((140000,), None),
    # a lane-aligned dim that is not the last moved minor (bert's
    # (L, d, heads, head_dim) at small widths), one ragged row tile
    ((2, 384, 5, 24), 0),
    # unstacked, several row tiles, a ragged last one (bert's embedding)
    ((1100, 256), None),
    # no dim a multiple of 128 (smollm's 960-wide leaves): the minor dim
    # is a whole tile's width, not a multiple of 128
    ((2, 120, 5, 24), 0),
    # the first dim minor and too wide for a tile: ragged row and column
    # tiles both (smollm's (49152, 960) embedding)
    ((9000, 40), None),
]


@pytest.mark.parametrize("shape,axis", LAMB_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lamb_kernel_matches_ref(shape, axis, dtype):
    x = jnp.asarray(RNG.standard_normal(shape), dtype)
    g = jnp.asarray(RNG.standard_normal(shape), dtype)
    m = jnp.asarray(RNG.standard_normal(shape), jnp.float32) * 0.1
    v = jnp.abs(jnp.asarray(RNG.standard_normal(shape), jnp.float32)) * 0.01
    kw = dict(lr=0.01, b1=0.9, b2=0.999, eps=1e-6, weight_decay=0.01)
    x1, m1, v1 = lamb_update(
        x, g, m, v, jnp.asarray(5), layer_axis=axis, interpret=True, **kw
    )[:3]
    x2, m2, v2 = lamb_update_ref(x, g, m, v, step=5, layer_axis=axis, **kw)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(x1, np.float32),
                               np.asarray(x2, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=3e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=3e-5, atol=1e-6)


def test_lamb_kernel_phi_bounds_and_no_trust():
    shape = (2, 500)
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32) * 10
    g = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    m = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    for kw in (dict(phi_bounds=(0.5, 2.0)), dict(apply_trust=False)):
        ref_kw = dict(lr=0.1, weight_decay=0.01, step=1, layer_axis=0, **kw)
        kern_kw = dict(lr=0.1, weight_decay=0.01, layer_axis=0, interpret=True)
        if "phi_bounds" in kw:
            kern_kw.update(phi_lo=kw["phi_bounds"][0], phi_hi=kw["phi_bounds"][1])
        else:
            kern_kw.update(apply_trust=False)
        x1 = lamb_update(x, g, m, v, jnp.asarray(1), **kern_kw)[0]
        x2, _, _ = lamb_update_ref(x, g, m, v, **ref_kw)
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                                   rtol=3e-5, atol=3e-6)


def _lamb_inputs(shape, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
    v = jnp.abs(jnp.asarray(rng.standard_normal(shape), jnp.float32)) * 0.01
    return x, g, m, v


@pytest.mark.parametrize("shape,axis,wd,trust", [
    ((2, 384, 5, 24), 0, 0.01, True),    # minor dim moved last, stacked
    ((96, 256), None, 0.0, False),       # unstacked, no decay, no trust
    ((2, 120, 5, 24), 0, 0.0, True),     # minor dim not a multiple of 128
    ((300,), None, 0.01, False),         # unstacked, one ragged tile
], ids=["lane-stacked", "lane-plain", "odd-stacked", "odd-plain"])
def test_lamb_kernel_ratio_and_update_sq(shape, axis, wd, trust):
    """The aux outputs: the applied trust ratio equals the oracle's, and
    the update's squared norm is ‖x' − x‖² of the kernel's own outputs."""
    x, g, m, v = _lamb_inputs(shape)
    kw = dict(lr=0.01, weight_decay=wd, apply_trust=trust, layer_axis=axis)
    x1, m1, v1, r1, usq = lamb_update(
        x, g, m, v, jnp.asarray(3), interpret=True, return_ratio=True, **kw)
    x2, m2, v2, r2 = lamb_update_ref(x, g, m, v, step=3, return_ratio=True,
                                     **kw)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                               rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=3e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=3e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-5)
    assert r1.shape == ((shape[0],) if axis == 0 else ())
    want = np.sum(np.square(np.asarray(x1, np.float64) - np.asarray(x)))
    np.testing.assert_allclose(float(usq), want, rtol=1e-4)


@pytest.mark.parametrize("shape,axis", [
    ((2, 384, 5, 24), 0), ((1100, 256), None), ((2, 120, 5, 24), 0),
    ((9000, 40), None),
], ids=["lane-stacked", "lane-ragged", "odd", "ragged-columns"])
def test_lamb_kernel_guard_skip_is_bit_identical(shape, axis):
    """ok=False writes every input back unchanged, non-finite gradients
    and all; ok=True is the unguarded update."""
    x, g, m, v = _lamb_inputs(shape, seed=1)
    g_bad = g.at[(0,) * len(shape)].set(jnp.nan)
    kw = dict(lr=0.01, layer_axis=axis, interpret=True)
    x1, m1, v1, usq = lamb_update(x, g_bad, m, v, jnp.asarray(2),
                                  ok=jnp.asarray(False), **kw)
    for got, want in ((x1, x), (m1, m), (v1, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(usq) == 0.0
    on = lamb_update(x, g, m, v, jnp.asarray(2), ok=jnp.asarray(True), **kw)
    free = lamb_update(x, g, m, v, jnp.asarray(2), **kw)
    for a, b in zip(on, free):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_fused_lamb_apply_update_norm_and_guard(mode):
    """Both backends: ``update_norm`` is ‖params' − params‖ over the tree,
    and a guarded skip returns params and moments bit-identical with a
    zero update norm."""
    from repro.kernels import fused_lamb_apply

    params = {"w": _lamb_inputs((2, 256, 4, 32))[0],
              "emb": _lamb_inputs((200, 128), seed=2)[0],
              "b": _lamb_inputs((8,), seed=3)[0]}
    grads = jax.tree.map(lambda p: p * 0.5 + 0.1, params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    la = {"w": 0, "emb": None, "b": None}
    kw = dict(layer_axes=la, mode=mode)
    x, m, v, unorm = fused_lamb_apply(params, grads, zeros, zeros,
                                      jnp.asarray(1), jnp.asarray(1e-2), **kw)
    want = np.sqrt(sum(np.sum(np.square(np.asarray(a, np.float64)
                                        - np.asarray(b)))
                       for a, b in zip(jax.tree.leaves(x),
                                       jax.tree.leaves(params))))
    np.testing.assert_allclose(float(unorm), want, rtol=1e-4)
    bad = jax.tree.map(lambda g: g.at[(0,) * g.ndim].set(jnp.inf), grads)
    x, m, v, unorm = fused_lamb_apply(params, bad, zeros, zeros,
                                      jnp.asarray(1), jnp.asarray(1e-2),
                                      ok=jnp.asarray(False), **kw)
    for got, want in ((x, params), (m, zeros), (v, zeros)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(unorm) == 0.0


def test_fused_lamb_transform_equals_core_lamb():
    from repro import core, optim

    params = {
        "stack": {"w": jnp.asarray(RNG.standard_normal((3, 24, 8)), jnp.float32)},
        "emb": jnp.asarray(RNG.standard_normal((64, 8)), jnp.float32),
        "norm": jnp.ones((8,), jnp.float32),
    }
    la = {"stack": {"w": 0}, "emb": -1, "norm": -1}
    tm = {"stack": {"w": True}, "emb": True, "norm": False}
    wm = {"stack": {"w": True}, "emb": True, "norm": False}
    sched = core.warmup_poly_decay(0.01, 50, 5)
    o1 = core.lamb(sched, weight_decay=0.01, layer_axes=la, trust_mask=tm,
                   wd_mask=wm)
    o2 = fused_lamb(sched, weight_decay=0.01, layer_axes=la, trust_mask=tm,
                    wd_mask=wm, interpret=True)
    s1, s2 = o1.init(params), o2.init(params)
    p1 = p2 = params
    for t in range(4):
        g = jax.tree.map(
            lambda x: jnp.asarray(RNG.standard_normal(x.shape), jnp.float32), params
        )
        u1, s1 = o1.update(g, s1, p1)
        p1 = optim.apply_updates(p1, u1)
        u2, s2 = o2.update(g, s2, p2)
        p2 = optim.apply_updates(p2, u2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_SHAPES = [
    (1, 2, 128, 128, 64, True),
    (2, 3, 256, 256, 32, True),
    (1, 1, 128, 384, 64, False),   # cross-length, non-causal
    (2, 2, 384, 384, 128, True),
]


@pytest.mark.parametrize("b,h,s,t,d,causal", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, h, s, t, d, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, h, t, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, h, t, d)), dtype)
    o1 = flash_attention(q, k, v, causal=causal, interpret=True)
    o2 = flash_attention_ref(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol, atol=tol)


def test_flash_gqa_layout_wrapper():
    b, s, h, hkv, d = 2, 128, 8, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    o1 = flash_sdpa(q, k, v, causal=True, interpret=True)
    kr = jnp.repeat(k, h // hkv, axis=2)
    vr = jnp.repeat(v, h // hkv, axis=2)
    o2 = flash_attention_ref(
        q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
        vr.transpose(0, 2, 1, 3), causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=3e-5, atol=3e-5)


def test_flash_rejects_indivisible_blocks():
    q = jnp.zeros((1, 1, 100, 64))
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)


@pytest.mark.parametrize("s,w", [(512, 128), (256, 64), (384, 256)])
def test_flash_attention_sliding_window(s, w):
    """Windowed flash kernel == dense-masked SWA reference.

    This is the kernel path that actually SAVES the SWA FLOPs by skipping
    out-of-window kv blocks (§Perf F1: a dense masked softmax saves none)."""
    q = jnp.asarray(RNG.standard_normal((1, 2, s, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, s, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, s, 64)), jnp.float32)
    o1 = flash_attention(q, k, v, causal=True, window=w, interpret=True)
    o2 = flash_attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# flash attention backward (custom VJP vs jax.grad through the dense oracle)
# ---------------------------------------------------------------------------

def _grad_case(make_flash, make_ref, args, tol):
    """max-abs-compare outputs and (dq, dk, dv) cotangents of a loss."""

    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32)))

    o1, o2 = make_flash(*args), make_ref(*args)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        rtol=tol, atol=tol)
    g1 = jax.grad(loss(make_flash), (0, 1, 2))(*args)
    g2 = jax.grad(loss(make_ref), (0, 1, 2))(*args)
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol, err_msg=f"d{name}")


# (b, h, hkv, s, d, causal, masked): causal/bidirectional × GQA × padding
FLASH_GRAD_CASES = [
    (1, 2, 2, 128, 32, True, False),
    (1, 2, 2, 128, 32, False, False),    # bidirectional (BERT MLM)
    (2, 4, 1, 128, 32, True, False),     # MQA
    (2, 4, 2, 128, 16, False, False),    # GQA bidirectional
    (2, 2, 2, 128, 32, False, True),     # padding mask, bidirectional
    (1, 4, 2, 256, 32, True, True),      # padding mask + GQA + causal
]


@pytest.mark.parametrize("b,h,hkv,s,d,causal,masked", FLASH_GRAD_CASES)
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_flash_grad_matches_ref(b, h, hkv, s, d, causal, masked, backend):
    """jax.grad through the flash custom-VJP ≡ grad through the dense
    softmax, for both the Pallas kernels (interpret) and the XLA scan."""
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    valid = (
        jnp.asarray(RNG.integers(s // 2, s + 1, size=(b,)), jnp.int32)
        if masked else None
    )
    rep = lambda x: jnp.repeat(x, h // hkv, axis=1)
    _grad_case(
        lambda q, k, v: flash_attention(
            q, k, v, valid, causal=causal, backend=backend),
        lambda q, k, v: flash_attention_ref(
            q, rep(k), rep(v), valid, causal=causal),
        (q, k, v), tol=3e-5,
    )


def test_flash_grad_window():
    """Sliding-window backward: recompute masks match the forward's."""
    q = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 32)), jnp.float32)
    _grad_case(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=100, interpret=True),
        lambda q, k, v: flash_attention_ref(q, k, v, causal=True, window=100),
        (q, k, v), tol=3e-5,
    )


def test_flash_window_plus_valid_fully_masked_rows():
    """window ∩ valid can be empty for pad rows (row - window >= valid):
    flash yields o = 0 and zero grads there (p forced to 0, not
    exp(NEG_INF - NEG_INF) = 1), and matches the dense reference exactly on
    every row that still has >= 1 valid key."""
    b, h, s, d, w = 2, 2, 256, 32, 64
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
    valid = jnp.asarray([40, s], jnp.int32)
    # causal+window row r attends (r-w, r] ∩ [0, valid): nonempty iff
    # r-w+1 <= valid-1, i.e. r <= valid + w - 2
    live = jnp.arange(s)[None, :] <= valid[:, None] + w - 2   # (b, s)
    lm = live[:, None, :, None].astype(jnp.float32)

    ref = flash_attention_ref(q, k, v, valid, causal=True, window=w)
    for backend in ("interpret", "xla"):
        o = flash_attention(q, k, v, valid, causal=True, window=w,
                            backend=backend)
        np.testing.assert_allclose(np.asarray(o * lm), np.asarray(ref * lm),
                                   rtol=3e-5, atol=3e-5)
        assert float(jnp.max(jnp.abs(o * (1 - lm)))) == 0.0  # dead rows: 0

        # gradients under a loss that (like real training) never consumes
        # fully-masked rows must match the dense reference
        def loss(f):
            return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)) * lm)

        g1 = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, valid, causal=True, window=w, backend=backend)),
            (0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(lambda q, k, v: flash_attention_ref(
            q, k, v, valid, causal=True, window=w)), (0, 1, 2))(q, k, v)
        for name, a, c in zip("qkv", g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=3e-5, atol=3e-5,
                                       err_msg=f"d{name} [{backend}]")


def test_flash_grad_bf16_inputs():
    """bf16 q/k/v: fp32 accumulators inside, bf16 cotangents out."""
    q = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=False, interpret=True).astype(jnp.float32)))(q)
    g2 = jax.grad(lambda q: jnp.sum(flash_attention_ref(
        q, k, v, causal=False).astype(jnp.float32)))(q)
    assert g1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g1, np.float32),
                               np.asarray(g2, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_sdpa_pads_ragged_lengths():
    """s=200 (not 128-divisible) no longer falls back: the wrapper pads to
    the block multiple, masks the pad rows, and slices — fwd and grads."""
    b, s, h, hkv, d = 2, 200, 4, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    rep = lambda x: jnp.repeat(x.transpose(0, 2, 1, 3), h // hkv, axis=1)
    _grad_case(
        lambda q, k, v: flash_sdpa(q, k, v, causal=False, interpret=True),
        lambda q, k, v: flash_attention_ref(
            q.transpose(0, 2, 1, 3), rep(k), rep(v), causal=False,
        ).transpose(0, 2, 1, 3),
        (q, k, v), tol=3e-5,
    )


def test_flash_sdpa_gqa_without_kv_repeat():
    """The GQA fold is structural: the wrapper and kernels never call
    jnp.repeat — grouped q heads share K/V tiles via the index maps — and
    the grouped result still matches the repeated-K/V dense reference."""
    import inspect

    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import ops as ops_mod

    assert "jnp.repeat(" not in inspect.getsource(ops_mod.flash_sdpa)
    assert "jnp.repeat(" not in inspect.getsource(fa_mod)

    b, s, h, hkv, d = 1, 128, 8, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    rep = lambda x: jnp.repeat(x.transpose(0, 2, 1, 3), h // hkv, axis=1)
    o2 = flash_attention_ref(
        q.transpose(0, 2, 1, 3), rep(k), rep(v), causal=True,
    ).transpose(0, 2, 1, 3)
    for backend in ("interpret", "xla"):
        o1 = flash_sdpa(q, k, v, causal=True, backend=backend)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=3e-5, atol=3e-5)


def test_flash_valid_length_matches_dense_bias():
    """attention-layer valid_len: flash path ≡ dense _mask_bias path."""
    from repro import nn
    from repro.configs.bert_large import smoke
    from repro.models.layers.attention import attention, attention_defs

    cfg = smoke().replace(use_flash_kernel=True)
    p = nn.init_params(attention_defs(cfg), jax.random.key(0))
    x = jnp.asarray(RNG.standard_normal((2, 128, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    # 0 = fully-padded example: both paths clamp to >= 1 key identically
    valid = jnp.asarray([0, 80], jnp.int32)
    y_flash, _ = attention(p, x, pos, cfg, valid_len=valid)
    y_dense, _ = attention(
        p, x, pos, cfg.replace(use_flash_kernel=False), valid_len=valid)
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)


def test_flash_sliding_window_layer_matches_dense():
    """SWA configs route through flash (the kernel supports window fwd+bwd);
    layer outputs match the dense positional-bias path."""
    from repro import nn
    from repro.configs.bert_large import smoke
    from repro.models.layers.attention import attention, attention_defs

    cfg = smoke().replace(
        use_flash_kernel=True, causal=True, sliding_window=48)
    p = nn.init_params(attention_defs(cfg), jax.random.key(0))
    x = jnp.asarray(RNG.standard_normal((2, 128, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    y_flash, _ = attention(p, x, pos, cfg)
    y_dense, _ = attention(p, x, pos, cfg.replace(use_flash_kernel=False))
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)


def test_flash_fallback_warns_once():
    """use_flash_kernel + unsupported feature ⇒ loud dense fallback."""
    import warnings

    from repro import nn
    from repro.models.layers import attention as attn_mod

    cfg = attn_mod.ModelConfig(
        name="warn-test", family="dense", n_layers=1, d_model=64, n_heads=2,
        n_kv_heads=2, d_ff=128, vocab_size=64, use_flash_kernel=True,
        logit_softcap=30.0, use_rope=False,
    )
    p = nn.init_params(attn_mod.attention_defs(cfg), jax.random.key(0))
    x = jnp.asarray(RNG.standard_normal((1, 16, 64)), jnp.float32)
    pos = jnp.arange(16)[None]
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        attn_mod.attention(p, x, pos, cfg)
        attn_mod.attention(p, x, pos, cfg)  # second call: deduped
    msgs = [str(w.message) for w in rec if "logit_softcap" in str(w.message)]
    assert len(msgs) == 1, msgs


def test_train_step_flash_equals_dense(tmp_path):
    """End-to-end: one train step of the MLM model with use_flash_kernel=True
    reproduces the dense-attention loss and gradients (CPU: XLA flash)."""
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.data import make_batch
    from repro.models import build_model
    from repro.train import make_train_step

    base = get_config("bert-large").replace(
        name="bert-flash-mini", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=256, activation_dtype="float32",
    )
    batch = jax.tree.map(
        jnp.asarray, make_batch(base, np.random.default_rng(0), 4, 128)
    )
    key = jax.random.key(0)
    states, metrics = [], []
    for flash in (True, False):
        cfg = base.replace(use_flash_kernel=flash)
        model = build_model(cfg)
        tc = TrainConfig(optimizer="lamb", grad_clip_norm=None)
        init_fn, step_fn = make_train_step(model, tc)
        st, m = jax.jit(step_fn)(init_fn(key), batch)
        states.append(st)
        metrics.append(m)
    assert float(metrics[0]["loss/total"]) == pytest.approx(
        float(metrics[1]["loss/total"]), rel=1e-5)
    assert float(metrics[0]["grad_norm"]) == pytest.approx(
        float(metrics[1]["grad_norm"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(states[0].params),
                    jax.tree.leaves(states[1].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
