"""Differentiable flash attention — Pallas TPU kernels (fwd + bwd).

Block-wise online-softmax attention: never materializes the (S, T) score
matrix, in the forward *or* the backward pass (the dominant train temp in
the dry-run memory analysis).  Three pieces share one ``jax.custom_vjp``:

  * **forward** — grid ``(batch*heads, q_blocks, kv_blocks)`` with the kv
    axis innermost; running max / denominator / accumulator live in VMEM
    scratch and the output tile is written once at the last kv block.  The
    forward also emits the per-row ``logsumexp`` residual the backward
    needs to recompute softmax probabilities block-locally.
  * **backward dq** — same grid as the forward; recomputes block logits
    from (q, k) + logsumexp, forms ``ds = p * (do·vᵀ - di)`` and
    accumulates ``dq += ds·k`` in fp32 VMEM scratch.
  * **backward dk/dv** — grid ``(batch*kv_heads, kv_blocks, group*q_blocks)``
    with the (q-head-in-group × q-block) axis innermost, so one grid cell
    owns a dk/dv tile and sums every query head of its GQA group into VMEM
    scratch — no materialized K/V repeat and no cross-cell races.

GQA is folded into the kernel index maps: q is ``(B, H, S, D)`` while k/v
stay ``(B, Hkv, T, D)``; the k/v BlockSpecs map each q head to its kv head
(``kv_head = head // (H // Hkv)``) so grouped heads *share* the K/V tiles
in VMEM instead of reading repeated copies from HBM.

Masking: ``causal`` (with the standard ``T - S`` row offset for
cross-length causal attention), sliding ``window``, and a per-example
``kv_valid`` length (keys at positions ``>= kv_valid[b]`` are masked for
every query row — this is the padding path that lets wrappers pad ragged
sequence lengths up to the 128-aligned block size).  Fully-masked kv
blocks are skipped via ``pl.when`` on block indices.

Backends: ``pallas`` (TPU), ``interpret`` (Pallas interpreter — tests),
and ``xla`` — a chunked ``lax.scan`` implementation of the *same* math
(same custom-VJP boundary, same residuals) that serves as the portable
CPU/GPU fallback, mirroring the ``fused_lamb`` backend scheme.

Block sizes default to (128, 128) q×kv tiles — MXU-aligned (128 lanes) and
small enough that q, k, v, acc tiles fit VMEM comfortably
(4 · 128 · head_dim · 4B ≈ 256 KiB at head_dim=128).  The per-row
residuals (``lse``, ``di``) travel as ``(B·H, 1, S)`` lane-dense rows and
``kv_valid`` sits whole in SMEM, so every block meets the TPU's (8, 128)
rule; ``tests/test_tpu_compile.py`` compiles the kernels for a v5e chip.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sharding.context import batch_local

NEG_INF = -1e30


class FlashSpec(NamedTuple):
    """Static (hashable) kernel configuration — the custom_vjp nondiff arg."""

    scale: float
    causal: bool
    window: int          # sliding-window size; 0 = full attention
    block_q: int
    block_k: int
    use_valid: bool      # apply the per-example kv_valid length mask
    backend: str         # "pallas" | "interpret" | "xla"


# ---------------------------------------------------------------------------
# shared mask algebra (kernels and XLA fallback use the same formulas)
# ---------------------------------------------------------------------------

def _mask_conds(spec: FlashSpec, rows, cols, offset: int, valid):
    """Boolean keep-mask over a (rows, cols) logits tile.

    ``rows``/``cols`` are absolute q/kv indices; ``offset = T - S`` aligns
    causal masking for cross-length attention (matches ``flash_attention_ref``).
    Returns None when nothing is masked (lets callers skip the select).
    """
    ok = None

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if spec.causal:
        ok = _and(ok, cols <= rows + offset)
    if spec.window:
        ok = _and(ok, cols > rows + offset - spec.window)
    if spec.use_valid:
        ok = _and(ok, cols < valid)
    return ok


def _block_run(spec: FlashSpec, qi, ki, offset: int, valid):
    """Whether a (q-block qi, kv-block ki) tile has any unmasked entry."""
    bq, bk = spec.block_q, spec.block_k
    run = None

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if spec.causal:
        # lowest kv col of the block must be <= highest causal col of the block
        run = _and(run, ki * bk <= (qi + 1) * bq - 1 + offset)
    if spec.window:
        # highest kv col must be inside the window of the highest q row
        run = _and(run, (ki + 1) * bk - 1 > qi * bq + offset - spec.window)
    if spec.use_valid:
        run = _and(run, ki * bk < valid)
    return run


def _maybe_when(run, body):
    if run is None:
        body()
    else:
        pl.when(run)(body)


def _valid(spec: FlashSpec, valid_ref, heads: int):
    """This grid cell's kv_valid length, read from the (B,) SMEM array
    (grid axis 0 enumerates batch × ``heads``)."""
    return valid_ref[pl.program_id(0) // heads] if spec.use_valid else None


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(
    valid_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *, spec: FlashSpec, offset: int, heads: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = spec.block_q, spec.block_k
    valid = _valid(spec, valid_ref, heads)

    @pl.when(ki == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * spec.scale                             # (bq, bk)
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _mask_conds(spec, rows, cols, offset, valid)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # (bq, bk)
        if ok is not None:
            # fully-masked rows (window ∩ valid can be empty for pad rows)
            # would otherwise see exp(NEG_INF - NEG_INF) = 1: force p = 0 so
            # such rows yield o = 0 and zero gradients instead of garbage
            p = jnp.where(ok, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    # skip kv blocks entirely above the diagonal (causal), entirely left of
    # the sliding window, or entirely past the valid kv length — THIS is
    # where the FLOP savings come from (a dense masked softmax saves none)
    _maybe_when(_block_run(spec, qi, ki, offset, valid), body)

    @pl.when(ki == nk - 1)
    def finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # the (bq, 1) column goes out lane-dense as one (1, bq) row
        lse_ref[0] = (m_ref[...] + jnp.log(l)).reshape(1, bq)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p_ds(spec, offset, valid, qi, ki, q, k, v, do, lse, di):
    """Block-local recompute shared by both backward kernels.

    Returns (p, ds) for one (bq, bk) tile: ``p = softmax(qkᵀ)`` rebuilt from
    the logsumexp residual, ``ds = p * (do·vᵀ - di)``.  ``lse`` and ``di``
    arrive as (1, bq) lane-dense rows and are used as (bq, 1) columns.
    """
    bq, bk = spec.block_q, spec.block_k
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * spec.scale                                 # (bq, bk)
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = _mask_conds(spec, rows, cols, offset, valid)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    p = jnp.exp(s - lse.reshape(bq, 1))            # (bq, bk), rows sum to 1
    if ok is not None:
        # fully-masked rows have lse ≈ NEG_INF, where exp(s - lse) != 0:
        # zero them so dk/dv/dq see exactly the forward's p = 0
        p = jnp.where(ok, p, 0.0)
    dp = jax.lax.dot_general(                      # do · vᵀ
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    ds = p * (dp - di.reshape(bq, 1))
    return p, ds


def _dq_kernel(
    valid_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
    acc_ref,
    *, spec: FlashSpec, offset: int, heads: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    valid = _valid(spec, valid_ref, heads)

    @pl.when(ki == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        _, ds = _recompute_p_ds(
            spec, offset, valid, qi, ki, q, k, v, do, lse_ref[0], di_ref[0]
        )
        acc_ref[...] += spec.scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    _maybe_when(_block_run(spec, qi, ki, offset, valid), body)

    @pl.when(ki == nk - 1)
    def finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    valid_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, spec: FlashSpec, offset: int, nq: int, heads: int,
):
    ki = pl.program_id(1)
    ti = pl.program_id(2)      # enumerates (head-in-group, q-block) pairs
    nt = pl.num_programs(2)
    qi = ti % nq
    valid = _valid(spec, valid_ref, heads)

    @pl.when(ti == 0)
    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _recompute_p_ds(
            spec, offset, valid, qi, ki, q, k, v, do, lse_ref[0], di_ref[0]
        )
        dv_acc[...] += jax.lax.dot_general(        # pᵀ · do
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        dk_acc[...] += spec.scale * jax.lax.dot_general(  # dsᵀ · q
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    _maybe_when(_block_run(spec, qi, ki, offset, valid), body)

    @pl.when(ti == nt - 1)
    def finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _kv_imap(h: int, hkv: int):
    """Map a flat q-head grid index to its (shared) kv-head block — the GQA
    fold: grouped q heads read the same K/V tile instead of a repeated copy."""
    group = h // hkv
    return lambda g, i, j: ((g // h) * hkv + (g % h) // group, j, 0)


# per-example kv_valid lengths: the whole (B,) int32 array sits in SMEM and
# each grid cell reads its own scalar (see ``_valid``)
_VALID_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _row_spec(bq: int, imap):
    """(1, 1, bq) block over a (G, 1, S) per-row array (lse, di): a
    lane-dense row, which meets the TPU's (8, 128) block rule."""
    return pl.BlockSpec((1, 1, bq), imap)


def _pallas_fwd(spec: FlashSpec, q, k, v, valid):
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    bq, bk = spec.block_q, spec.block_k
    interpret = spec.backend == "interpret"
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hkv, t, d)
    vf = v.reshape(b * hkv, t, d)

    grid = (b * h, s // bq, t // bk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec, offset=t - s, heads=h),
        grid=grid,
        in_specs=[
            _VALID_SPEC,
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), _kv_imap(h, hkv)),
            pl.BlockSpec((1, bk, d), _kv_imap(h, hkv)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            _row_spec(bq, lambda g, i, j: (g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # denominator l
        ],
        interpret=interpret,
        name="flash_fwd",
    )(valid, qf, kf, vf)
    return o.reshape(b, h, s, d), lse.reshape(b, h, s)


def _pallas_bwd(spec: FlashSpec, q, k, v, valid, o, lse, do):
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = h // hkv
    bq, bk = spec.block_q, spec.block_k
    nq, nk = s // bq, t // bk
    interpret = spec.backend == "interpret"
    offset = t - s

    # di = rowwise(o · do) — needed by both kernels; cheap fp32 jnp reduction
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hkv, t, d)
    vf = v.reshape(b * hkv, t, d)
    dof = do.reshape(b * h, s, d)
    lsef = lse.reshape(b * h, 1, s)
    dif = di.reshape(b * h, 1, s)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, spec=spec, offset=offset, heads=h),
        grid=(b * h, nq, nk),
        in_specs=[
            _VALID_SPEC,
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), _kv_imap(h, hkv)),
            pl.BlockSpec((1, bk, d), _kv_imap(h, hkv)),
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            _row_spec(bq, lambda g, i, j: (g, 0, i)),
            _row_spec(bq, lambda g, i, j: (g, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(valid, qf, kf, vf, dof, lsef, dif)

    # dk/dv: one grid cell per kv tile; the innermost axis walks every
    # (q head of the GQA group × q block), summing into VMEM scratch
    def q_head(n, ti):
        return (n // hkv) * h + (n % hkv) * group + ti // nq

    def q_imap(n, jk, ti):
        return (q_head(n, ti), ti % nq, 0)

    def qrow_imap(n, jk, ti):
        return (q_head(n, ti), 0, ti % nq)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, spec=spec, offset=offset, nq=nq,
                          heads=hkv),
        grid=(b * hkv, nk, group * nq),
        in_specs=[
            _VALID_SPEC,
            pl.BlockSpec((1, bq, d), q_imap),
            pl.BlockSpec((1, bk, d), lambda n, jk, ti: (n, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda n, jk, ti: (n, jk, 0)),
            pl.BlockSpec((1, bq, d), q_imap),
            _row_spec(bq, qrow_imap),
            _row_spec(bq, qrow_imap),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda n, jk, ti: (n, jk, 0)),
            pl.BlockSpec((1, bk, d), lambda n, jk, ti: (n, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),   # dk accumulator
            pltpu.VMEM((bk, d), jnp.float32),   # dv accumulator
        ],
        interpret=interpret,
        name="flash_dkv",
    )(valid, qf, kf, vf, dof, lsef, dif)

    return (
        dq.reshape(b, h, s, d),
        dk.reshape(b, hkv, t, d),
        dv.reshape(b, hkv, t, d),
    )


# ---------------------------------------------------------------------------
# XLA fallback: the same chunked online-softmax math as a lax.scan —
# portable to CPU/GPU, and the backward below recomputes block logits from
# the logsumexp residual exactly like the Pallas kernels (same VJP boundary,
# so memory stays O(S·block) instead of O(S·T) on every backend).
# ---------------------------------------------------------------------------

def _xla_chunks(spec: FlashSpec, k):
    """Pad kv to a block multiple and reshape to scan chunks (nk leading)."""
    b, hkv, t, d = k.shape
    bk = spec.block_k
    pad = -t % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (t + pad) // bk
    return k.reshape(b, hkv, nk, bk, d).transpose(2, 0, 1, 3, 4), nk


def _xla_mask(spec: FlashSpec, j, s, t, valid, offset):
    """(B, 1, 1, S, bk) keep-mask for kv chunk j (None if nothing masked)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (s, spec.block_k), 0)
    cols = j * spec.block_k + jax.lax.broadcasted_iota(
        jnp.int32, (s, spec.block_k), 1
    )
    geo = _mask_conds(spec._replace(use_valid=False), rows, cols, offset, None)
    has_pad = bool(-t % spec.block_k)  # kv pad from _xla_chunks: always masked
    if not spec.use_valid and not has_pad:
        return None if geo is None else geo[None, None, None]
    lim = jnp.minimum(valid, t) if spec.use_valid else jnp.full_like(valid, t)
    ok = cols[None] < lim[:, None, None]            # (B, S, bk)
    if geo is not None:
        ok = jnp.logical_and(ok, geo[None])
    return ok[:, None, None]


def _xla_fwd(spec: FlashSpec, q, k, v, valid):
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    offset = t - s
    qg = q.reshape(b, hkv, g, s, d).astype(jnp.float32)
    kc, nk = _xla_chunks(spec, k)
    vc, _ = _xla_chunks(spec, v)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        sij = jnp.einsum(
            "bngsd,bntd->bngst", qg, kj.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * spec.scale
        ok = _xla_mask(spec, j, s, t, valid, offset)
        if ok is not None:
            sij = jnp.where(ok, sij, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sij, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sij - m_new[..., None])
        if ok is not None:
            p = jnp.where(ok, p, 0.0)   # fully-masked rows: p = 0, not 1
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bngst,bntd->bngsd", p, vj.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    init = (
        jnp.full((b, hkv, g, s), NEG_INF, jnp.float32),
        jnp.zeros((b, hkv, g, s), jnp.float32),
        jnp.zeros((b, hkv, g, s, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, jnp.arange(nk)))
    l = jnp.maximum(l, 1e-30)
    o = (acc / l[..., None]).reshape(b, h, s, d).astype(q.dtype)
    lse = (m + jnp.log(l)).reshape(b, h, s)
    return o, lse


def _xla_bwd(spec: FlashSpec, q, k, v, valid, o, lse, do):
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    bk = spec.block_k
    offset = t - s
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    qg = q.reshape(b, hkv, g, s, d).astype(jnp.float32)
    dog = do.reshape(b, hkv, g, s, d).astype(jnp.float32)
    lseg = lse.reshape(b, hkv, g, s)
    dig = di.reshape(b, hkv, g, s)
    kc, nk = _xla_chunks(spec, k)
    vc, _ = _xla_chunks(spec, v)

    def body(dq, xs):
        kj, vj, j = xs
        sij = jnp.einsum(
            "bngsd,bntd->bngst", qg, kj.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * spec.scale
        ok = _xla_mask(spec, j, s, t, valid, offset)
        if ok is not None:
            sij = jnp.where(ok, sij, NEG_INF)
        p = jnp.exp(sij - lseg[..., None])          # (b,n,g,s,bk)
        if ok is not None:
            # fully-masked rows have lse ≈ NEG_INF: zero p as in the forward
            p = jnp.where(ok, p, 0.0)
        dp = jnp.einsum(
            "bngsd,bntd->bngst", dog, vj.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dig[..., None])
        dkj = spec.scale * jnp.einsum("bngst,bngsd->bntd", ds, qg)
        dvj = jnp.einsum("bngst,bngsd->bntd", p, dog)
        dq = dq + spec.scale * jnp.einsum(
            "bngst,bntd->bngsd", ds, kj.astype(jnp.float32)
        )
        return dq, (dkj, dvj)

    dq0 = jnp.zeros((b, hkv, g, s, d), jnp.float32)
    dq, (dkc, dvc) = jax.lax.scan(body, dq0, (kc, vc, jnp.arange(nk)))
    dk = dkc.transpose(1, 2, 0, 3, 4).reshape(b, hkv, nk * bk, d)[:, :, :t]
    dv = dvc.transpose(1, 2, 0, 3, 4).reshape(b, hkv, nk * bk, d)[:, :, :t]
    return (
        dq.reshape(b, h, s, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


# ---------------------------------------------------------------------------
# custom VJP: one boundary, three backends
# ---------------------------------------------------------------------------

def _fwd_impl(spec: FlashSpec, q, k, v, valid):
    if spec.backend == "xla":
        return _xla_fwd(spec, q, k, v, valid)
    return _pallas_fwd(spec, q, k, v, valid)


def _bwd_impl(spec: FlashSpec, q, k, v, valid, o, lse, do):
    if spec.backend == "xla":
        return _xla_bwd(spec, q, k, v, valid, o, lse, do)
    return _pallas_bwd(spec, q, k, v, valid, o, lse, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(spec: FlashSpec, q, k, v, valid):
    o, _ = _fwd_impl(spec, q, k, v, valid)
    return o


def _flash_fwd(spec: FlashSpec, q, k, v, valid):
    o, lse = _fwd_impl(spec, q, k, v, valid)
    return o, (q, k, v, valid, o, lse)


def _flash_bwd(spec: FlashSpec, res, do):
    q, k, v, valid, o, lse = res
    dq, dk, dv = _bwd_impl(spec, q, k, v, valid, o, lse, do)
    # valid lengths are integers: symbolically-zero cotangent
    return dq, dk, dv, np.zeros(valid.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, Hkv, T, D) — Hkv must divide H (GQA)
    v: jnp.ndarray,  # (B, Hkv, T, D)
    kv_valid: Optional[jnp.ndarray] = None,  # (B,) int32 valid kv lengths
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int = 0,   # sliding-window size; 0 = full attention
    backend: str = "pallas",  # pallas | interpret | xla
) -> jnp.ndarray:
    """Differentiable flash attention; ``jax.grad`` works through it.

    Sequence lengths must divide the (possibly clamped) block sizes —
    ``flash_sdpa`` pads ragged lengths and masks the pad via ``kv_valid``.
    Keys at positions ``>= kv_valid[b]`` are masked out for every query row
    of example ``b`` (bidirectional padding / ragged-batch support).

    Under a sharding context the Pallas backends run per data shard
    (``sharding.context.batch_local``): attention is local to each example.
    """
    if interpret and backend == "pallas":
        backend = "interpret"
    call = functools.partial(
        _flash_attention, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, window=window, backend=backend,
    )
    args = (q, k, v) if kv_valid is None else (q, k, v, kv_valid)
    if backend == "xla":
        return call(*args)
    return batch_local(call, *args)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "window",
                     "backend"),
)
def _flash_attention(q, k, v, kv_valid=None, *, causal, scale, block_q,
                     block_k, window, backend):
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    if h % max(hkv, 1):
        raise ValueError(f"n_heads {h} not a multiple of kv heads {hkv}")
    scale = scale if scale is not None else 1.0 / (d**0.5)
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    if backend not in ("pallas", "interpret", "xla"):
        raise ValueError(f"unknown flash backend {backend!r}")
    if backend != "xla" and (s % block_q or t % block_k):
        # the xla scan pads/masks its own kv chunks and has no q tiling
        raise ValueError(
            f"seq lens ({s},{t}) must divide blocks ({block_q},{block_k})"
        )

    use_valid = kv_valid is not None
    valid = (
        jnp.clip(kv_valid.astype(jnp.int32), 1, t)
        if use_valid
        else jnp.full((b,), t, jnp.int32)
    )
    spec = FlashSpec(
        scale=float(scale), causal=causal, window=window,
        block_q=block_q, block_k=block_k, use_valid=use_valid,
        backend=backend,
    )
    return _flash(spec, q, k, v, valid)
