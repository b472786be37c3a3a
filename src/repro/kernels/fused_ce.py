"""Fused chunked-vocab cross-entropy — the MLM head without the logits.

The LM head is the dominant activation-memory term of the BERT train step:
a dense head projects every position to the vocab and takes an fp32
``log_softmax`` over a ``(B, S, V)`` tensor, even though MLM supervises
only ~15% of positions.  This module is the second half of the fused-head
path (the first — gathering supervised positions *before* the projection —
lives in ``train/loss.py``): given already-gathered rows ``h`` of shape
``(N, D)`` and the vocab projection ``w`` of shape ``(V, D)``, it streams
vocab chunks through projection + online log-sum-exp so the ``(N, V)``
logits tensor never exists, forward *or* backward.

Three pieces share one ``jax.custom_vjp`` (the PR-3 flash-attention
pattern):

  * **forward** — grid ``(row_blocks, vocab_chunks)`` with the vocab axis
    innermost; running max / denominator / label-logit / argmax statistics
    live in fp32 VMEM scratch and the per-row ``(nll, correct, lse)``
    outputs are written once at the last chunk.  ``lse`` is the only
    residual the backward needs.
  * **backward d_hidden** — same grid; recomputes the chunk's softmax
    probabilities from ``p = exp(h·w_cᵀ - lse)``, forms
    ``dlogits = (p - onehot(label)) · g`` and accumulates
    ``dh += dlogits · w_c`` in VMEM scratch.
  * **backward d_w** — grid ``(vocab_chunks, row_blocks)`` with the row
    axis innermost: one grid cell owns a ``(block_v, D)`` weight-gradient
    tile and sums every row block into it (``dw_c += dlogitsᵀ · h``) — the
    per-chunk ``(d_hidden, d_W_vocab)`` emission the fused head needs.

All statistics and accumulators are fp32 regardless of the input dtype
(bf16 rows/weights are upcast per tile), mirroring the mixed-precision
policy of the dense loss (``log_softmax`` in fp32).

Backends: ``pallas`` (TPU), ``interpret`` (Pallas interpreter — tests),
and ``xla`` — a chunked ``lax.scan`` of the *same* math (same custom-VJP
boundary, same ``lse`` residual) that is the portable CPU/GPU default,
resolved by :func:`resolve_ce_backend` exactly like
``resolve_flash_backend`` / ``resolve_fused_backend``.  Because the
reductions in the XLA backend are plain jnp, GSPMD keeps the vocab-chunk
log-sum-exp and both weight-gradient reductions *global* when ``w`` or
``h`` are sharded over a mesh.  The kernels cannot be partitioned by
GSPMD; on a mesh ``train/loss.py`` runs them per data shard under
``shard_map`` (``sharding.context.batch_local``).

Per-row vectors (labels, ``g``, and the ``nll`` / ``correct`` / ``lse``
outputs) travel as ``(1, N)`` rows in ``(1, block_n)`` blocks: lane-dense,
which meets the TPU's (8, 128) block rule where a 1-D block does not.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sharding.context import batch_local

NEG_INF = -1e30
_IDX_INF = np.iinfo(np.int32).max


class CESpec(NamedTuple):
    """Static (hashable) kernel configuration — the custom_vjp nondiff arg."""

    block_n: int   # rows per tile
    block_v: int   # vocab columns per chunk
    vocab: int     # true vocab size; columns >= vocab are padding
    backend: str   # "pallas" | "interpret" | "xla"


def resolve_ce_backend(backend: str = "auto") -> str:
    """Map ``auto`` to the fastest correct CE backend for this platform.

    Mirrors :func:`repro.kernels.ops.resolve_flash_backend`: the Pallas
    kernels only come back on TPU; elsewhere the chunked-``lax.scan`` XLA
    implementation (same custom-VJP math, portable) is the default, and
    ``interpret`` runs the Pallas kernels under the interpreter (tests).
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla", "interpret"):
        raise ValueError(f"unknown fused-CE backend {backend!r}")
    return backend


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(
    h_ref, w_ref, lbl_ref, nll_ref, corr_ref, lse_ref,
    m_ref, l_ref, ll_ref, bmax_ref, bidx_ref,
    *, spec: CESpec,
):
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    bn, bv = spec.block_n, spec.block_v

    @pl.when(j == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        ll_ref[...] = jnp.full_like(ll_ref, NEG_INF)
        bmax_ref[...] = jnp.full_like(bmax_ref, NEG_INF)
        bidx_ref[...] = jnp.zeros_like(bidx_ref)

    h = h_ref[...].astype(jnp.float32)            # (bn, d)
    w = w_ref[...].astype(jnp.float32)            # (bv, d)
    s = jax.lax.dot_general(                      # (bn, bv) chunk logits
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    # every chunk in the grid has >= 1 real column (vocab padding < block_v),
    # so the running max below stays finite
    s = jnp.where(cols < spec.vocab, s, NEG_INF)

    m_prev = m_ref[...]                           # (bn, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                        # padded cols underflow to 0
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new

    lbl = lbl_ref[...].reshape(bn, 1)             # int32 in [0, vocab)
    hit = cols == lbl
    ll_ref[...] = jnp.where(                      # label logit: set exactly once
        jnp.any(hit, axis=1, keepdims=True),
        jnp.sum(jnp.where(hit, s, 0.0), axis=1, keepdims=True),
        ll_ref[...],
    )
    # running argmax with first-occurrence tie-breaking (jnp.argmax semantics):
    # within the chunk take the lowest column achieving the max; across chunks
    # a strict > keeps the earlier chunk's winner
    cand = jnp.min(jnp.where(s == m_cur, cols, _IDX_INF), axis=1, keepdims=True)
    better = m_cur > bmax_ref[...]
    bidx_ref[...] = jnp.where(better, cand, bidx_ref[...])
    bmax_ref[...] = jnp.maximum(bmax_ref[...], m_cur)

    @pl.when(j == nv - 1)
    def finish():
        # (bn, 1) columns go out lane-dense as (1, bn) rows
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[...] = lse.reshape(1, bn)
        nll_ref[...] = (lse - ll_ref[...]).reshape(1, bn)
        corr_ref[...] = (bidx_ref[...] == lbl).astype(jnp.float32).reshape(1, bn)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _chunk_dlogits(spec: CESpec, j, h, w, lbl, g, lse):
    """(p - onehot(label)) · g for one (bn, bv) tile, rebuilt from ``lse``.

    ``lbl``, ``g`` and ``lse`` arrive as (1, bn) lane-dense rows and are
    used as (bn, 1) columns.
    """
    bn, bv = spec.block_n, spec.block_v
    s = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    s = jnp.where(cols < spec.vocab, s, NEG_INF)
    p = jnp.exp(s - lse.reshape(bn, 1))           # padded cols -> 0
    onehot = (cols == lbl.reshape(bn, 1)).astype(jnp.float32)
    return (p - onehot) * g.reshape(bn, 1)


def _dh_kernel(
    h_ref, w_ref, lbl_ref, g_ref, lse_ref, dh_ref, acc_ref, *, spec: CESpec
):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    dlog = _chunk_dlogits(spec, j, h, w, lbl_ref[...], g_ref[...], lse_ref[...])
    acc_ref[...] += jax.lax.dot_general(          # (bn, d)
        dlog, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(j == nv - 1)
    def finish():
        dh_ref[...] = acc_ref[...].astype(dh_ref.dtype)


def _dw_kernel(
    h_ref, w_ref, lbl_ref, g_ref, lse_ref, dw_ref, acc_ref, *, spec: CESpec
):
    i = pl.program_id(0)       # vocab chunk (owns the dw tile)
    t = pl.program_id(1)       # row block, innermost
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    dlog = _chunk_dlogits(spec, i, h, w, lbl_ref[...], g_ref[...], lse_ref[...])
    acc_ref[...] += jax.lax.dot_general(          # dlogᵀ · h  -> (bv, d)
        dlog, h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(t == nt - 1)
    def finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _pallas_fwd(spec: CESpec, h, w, lbl):
    n, d = h.shape
    vp = w.shape[0]
    bn, bv = spec.block_n, spec.block_v
    interpret = spec.backend == "interpret"
    row = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    vec = jax.ShapeDtypeStruct((1, n), jnp.float32)
    nll, corr, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec),
        grid=(n // bn, vp // bv),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            row,
        ],
        out_specs=[row] * 3,
        out_shape=[vec, vec, vec],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),   # running max m
            pltpu.VMEM((bn, 1), jnp.float32),   # denominator l
            pltpu.VMEM((bn, 1), jnp.float32),   # label logit
            pltpu.VMEM((bn, 1), jnp.float32),   # best (argmax) value
            pltpu.VMEM((bn, 1), jnp.int32),     # best (argmax) index
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(h, w, lbl.reshape(1, n))
    return nll[0], corr[0], lse[0]


def _pallas_bwd(spec: CESpec, h, w, lbl, lse, g):
    n, d = h.shape
    vp = w.shape[0]
    bn, bv = spec.block_n, spec.block_v
    interpret = spec.backend == "interpret"
    rows = (lbl.reshape(1, n), g.reshape(1, n), lse.reshape(1, n))

    row = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, spec=spec),
        grid=(n // bn, vp // bv),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dh",
    )(h, w, *rows)

    row_t = pl.BlockSpec((1, bn), lambda i, t: (0, t))
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, spec=spec),
        grid=(vp // bv, n // bn),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, t: (t, 0)),
            pl.BlockSpec((bv, d), lambda i, t: (i, 0)),
            row_t, row_t, row_t,
        ],
        out_specs=pl.BlockSpec((bv, d), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((vp, d), w.dtype),
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dw",
    )(h, w, *rows)
    return dh, dw


# ---------------------------------------------------------------------------
# XLA fallback: the same chunked online-LSE math as a lax.scan — portable to
# CPU/GPU, same custom-VJP boundary/residuals, and memory O(N·block_v)
# instead of O(N·V) on every backend.
# ---------------------------------------------------------------------------

def _xla_chunks(spec: CESpec, w):
    nv = w.shape[0] // spec.block_v
    return w.reshape(nv, spec.block_v, w.shape[1]), nv


def _xla_fwd(spec: CESpec, h, w, lbl):
    n = h.shape[0]
    hf = h.astype(jnp.float32)
    wc, nv = _xla_chunks(spec, w)
    bv = spec.block_v

    def body(carry, xs):
        m, l, ll, bmax, bidx = carry
        wj, j = xs
        s = jax.lax.dot_general(
            hf, wj.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # (n, bv)
        cols = j * bv + jnp.arange(bv, dtype=jnp.int32)
        s = jnp.where(cols[None, :] < spec.vocab, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l = alpha * l + jnp.sum(p, axis=1)
        hit = cols[None, :] == lbl[:, None]
        ll = jnp.where(
            jnp.any(hit, axis=1), jnp.sum(jnp.where(hit, s, 0.0), axis=1), ll
        )
        cand = jnp.min(
            jnp.where(s == m_cur[:, None], cols[None, :], _IDX_INF), axis=1
        )
        better = m_cur > bmax
        bidx = jnp.where(better, cand, bidx)
        bmax = jnp.maximum(bmax, m_cur)
        return (m_new, l, ll, bmax, bidx), None

    init = (
        jnp.full((n,), NEG_INF, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.full((n,), NEG_INF, jnp.float32),
        jnp.full((n,), NEG_INF, jnp.float32),
        jnp.zeros((n,), jnp.int32),
    )
    (m, l, ll, bmax, bidx), _ = jax.lax.scan(
        body, init, (wc, jnp.arange(nv))
    )
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return lse - ll, (bidx == lbl).astype(jnp.float32), lse


def _xla_bwd(spec: CESpec, h, w, lbl, lse, g):
    hf = h.astype(jnp.float32)
    wc, nv = _xla_chunks(spec, w)
    bv = spec.block_v

    def body(dh, xs):
        wj, j = xs
        wjf = wj.astype(jnp.float32)
        s = jax.lax.dot_general(
            hf, wjf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        cols = j * bv + jnp.arange(bv, dtype=jnp.int32)
        s = jnp.where(cols[None, :] < spec.vocab, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        onehot = (cols[None, :] == lbl[:, None]).astype(jnp.float32)
        dlog = (p - onehot) * g[:, None]
        dwj = jax.lax.dot_general(                 # (bv, d) per-chunk emission
            dlog, hf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dh = dh + jax.lax.dot_general(
            dlog, wjf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dh, dwj

    dh0 = jnp.zeros(hf.shape, jnp.float32)
    dh, dwc = jax.lax.scan(body, dh0, (wc, jnp.arange(nv)))
    dw = dwc.reshape(-1, h.shape[1])
    return dh.astype(h.dtype), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# custom VJP: one boundary, three backends
# ---------------------------------------------------------------------------

def _fwd_impl(spec: CESpec, h, w, lbl):
    if spec.backend == "xla":
        return _xla_fwd(spec, h, w, lbl)
    return _pallas_fwd(spec, h, w, lbl)

def _bwd_impl(spec: CESpec, h, w, lbl, lse, g):
    if spec.backend == "xla":
        return _xla_bwd(spec, h, w, lbl, lse, g)
    return _pallas_bwd(spec, h, w, lbl, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_ce(spec: CESpec, h, w, lbl):
    nll, correct, _ = _fwd_impl(spec, h, w, lbl)
    return nll, correct


def _fused_ce_fwd(spec: CESpec, h, w, lbl):
    nll, correct, lse = _fwd_impl(spec, h, w, lbl)
    return (nll, correct), (h, w, lbl, lse)


def _fused_ce_bwd(spec: CESpec, res, cts):
    h, w, lbl, lse = res
    d_nll, _d_correct = cts   # ``correct`` is piecewise constant: grad 0 a.e.
    dh, dw = _bwd_impl(spec, h, w, lbl, lse, d_nll.astype(jnp.float32))
    # labels are integers: symbolically-zero cotangent
    return dh, dw, np.zeros(lbl.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def fused_ce(
    h: jnp.ndarray,        # (N, D) gathered rows (any float dtype)
    w: jnp.ndarray,        # (V, D) vocab projection, embedding layout
    labels: jnp.ndarray,   # (N,) int targets; clipped into [0, V)
    *,
    backend: str = "auto",     # auto | pallas | interpret | xla
    block_n: int = 128,
    block_v: int = 512,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row ``(nll, correct)`` without materializing the (N, V) logits.

    ``nll[i] = logsumexp_v(h[i]·w[v]) - h[i]·w[labels[i]]`` in fp32;
    ``correct[i] = argmax_v(h[i]·w[v]) == labels[i]`` with ``jnp.argmax``
    first-occurrence tie semantics.  Differentiable w.r.t. ``h`` and ``w``
    through ``jax.custom_vjp`` (``correct`` contributes zero gradient).

    Rows the caller wants ignored should simply receive zero cotangent
    (multiply their ``nll`` by a 0 weight in the loss) — their ``dh``/``dw``
    contributions then vanish exactly.  The weight is expected in the
    ``(V, D)`` embedding layout; transpose a ``(D, V)`` unembed matrix
    before calling.

    Under a sharding context the Pallas backends run per data shard
    (``sharding.context.batch_local``): the rows are split over the batch
    axes, ``w`` is replicated into every shard, and its gradient is summed
    over them.
    """
    n, d = h.shape
    if w.shape[1] != d:
        raise ValueError(f"h feature dim {d} != w feature dim {w.shape[1]}")
    if labels.shape != (n,):
        raise ValueError(f"labels shape {labels.shape} != ({n},)")
    if interpret:
        if backend == "xla":
            raise ValueError("interpret=True conflicts with backend='xla'")
        mode = "interpret"
    else:
        mode = resolve_ce_backend(backend)
    call = functools.partial(_fused_ce_rows, mode=mode, block_n=block_n,
                             block_v=block_v)
    if mode == "xla":
        return call(h, labels, w)
    return batch_local(call, h, labels, shared=(w,))


@functools.partial(jax.jit, static_argnames=("mode", "block_n", "block_v"))
def _fused_ce_rows(h, labels, w, *, mode, block_n, block_v):
    n = h.shape[0]
    v = w.shape[0]
    lbl = jnp.clip(labels.astype(jnp.int32), 0, v - 1)
    bv = min(block_v, v)
    pad_v = -v % bv
    if pad_v:  # padded vocab columns are masked via spec.vocab
        w = jnp.pad(w, ((0, pad_v), (0, 0)))
    bn = min(block_n, n)
    pad_n = -n % bn if mode != "xla" else 0
    if pad_n:  # pad rows are sliced off below; their cotangents are zero,
        # so dh pad rows vanish and dw never sees them (g = 0)
        h = jnp.pad(h, ((0, pad_n), (0, 0)))
        lbl = jnp.pad(lbl, (0, pad_n))

    spec = CESpec(block_n=bn, block_v=bv, vocab=v, backend=mode)
    nll, correct = _fused_ce(spec, h, w, lbl)
    if pad_n:
        nll, correct = nll[:n], correct[:n]
    return nll, correct
