"""Fused LAMB update — Pallas TPU kernel.

The optimizer step is HBM-bandwidth bound: naively expressed in XLA it makes
~11 full passes over model-sized arrays (m/v EMA updates, bias correction,
ratio, weight decay, two norm reductions, apply), and the global norm
reductions split the fusion.  This kernel does it in two structured passes
over each leaf, in place and in the layout the step holds it:

  pass A (``lamb_moments``): read g, x, m, v → write m', v' over m, v and
      accumulate per-layer partial sums of ‖x‖² and ‖u‖² (u = r + wd·x
      recomputed from m', v') into a resident ``(8, tc)`` block per layer.
  (host) per-layer trust ratio = phi(‖x‖)/‖u‖.
  pass B (``lamb_apply``): read x, m', v' + ratio → write x' over x (u
      recomputed; cheaper than writing a param-sized u temp in pass A), and
      sum ‖x' − x‖² per layer, so the step's update norm needs no second
      read of the old weights (which the in-place write has replaced).

Total traffic ≈ 10 N  vs ≈ 21 N unfused.  The stacked-layers axis is grid
dim 0, giving exact per-layer (scan-aware) trust ratios.  The
bias-correction scalars, the non-finite guard and the per-layer ratios
are read from SMEM.

**The view.**  LAMB is elementwise but for two sums per layer, so any
element order within a layer serves, as long as every operand shares it.
Each leaf is seen as ``(layers, rows, C)``: its dims in the order the TPU's
default layout holds them (:func:`held_order`), ``C`` the minor one, the
rest folded into rows.  On the chip that transpose and reshape are a
bitcast of the leaf's own layout, so no pass relayouts it.  Tiles are
``(1, tr, tc)``: ``tc`` is ``C`` (legal as the full dim, a multiple of 128
or not), or a multiple of 128 where ``C`` is too wide for a tile of
``ROW_ALIGN`` rows within ``block``.  Where a tile runs past the end of
``rows`` or ``C`` it is ragged: the elements past the end are masked out
of the sums and their writes are dropped, so nothing is padded.  The sums
fold each tile's rows into a resident ``(8, tc)`` block per layer.

**In place.**  m → m', v → v' and x → x' alias (``input_output_aliases``),
so a step that donates its state updates it without a copy.  On a step the
guard ``ok`` skips, each kernel writes its inputs back, bit-identical.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sharding.context import batch_local

LANES = 128
SUBLANES = 8
ROW_ALIGN = 16  # tile rows: whole (8, 128) f32 and (16, 128) bf16 tiles
DEFAULT_BLOCK = 128 * 1024  # elements per tile: 512 KiB / f32 operand

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def held_order(dims) -> Tuple[int, ...]:
    """The dims of a layer of shape ``dims`` in the order the TPU's default
    layout holds them, major to minor.

    The layout tiles its two minor dims by (8, 128) and takes the pair that
    pads least; on a tie the later dims, so row-major where that pads no
    more.  The other dims keep their order before them.  For bert-large's
    ``wq`` (1024, 16, 64) that is (16, 64, 1024); for smollm-360m's
    embedding (49152, 960), (960, 49152).
    """
    n = len(dims)
    if n < 2:
        return tuple(range(n))

    def padded(c, s):
        rest = math.prod(d for i, d in enumerate(dims) if i not in (c, s))
        return (rest * pl.cdiv(dims[s], SUBLANES) * SUBLANES
                * pl.cdiv(dims[c], LANES) * LANES)

    _, c, s = min((padded(c, s), -c, -s)
                  for c in range(n) for s in range(n) if s != c)
    c, s = -c, -s
    return tuple(i for i in range(n) if i not in (c, s)) + (s, c)


def _sq_sum(a: jnp.ndarray, rows: Optional[int],
            cols: Optional[int]) -> jnp.ndarray:
    """(tr, tc) → (8, tc) partial sums of a² (vreg adds over row groups),
    the elements at or past ``rows``/``cols`` of a ragged tile left out."""
    sq = a * a
    valid = None
    if rows is not None:
        row = (jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
               + pl.program_id(1) * a.shape[0])
        valid = row < rows
    if cols is not None:
        col = (jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
               + pl.program_id(2) * a.shape[1])
        valid = col < cols if valid is None else valid & (col < cols)
    if valid is not None:
        sq = jnp.where(valid, sq, 0.0)  # past the end: stale, maybe NaN
    return jnp.sum(sq.reshape(-1, SUBLANES, a.shape[-1]), axis=0)


def _first_tile():
    return (pl.program_id(1) == 0) & (pl.program_id(2) == 0)


def _moments_kernel(
    c_ref, x_ref, g_ref, m_ref, v_ref,
    m_out, v_out, xsq_out, usq_out,
    *, b1: float, b2: float, eps: float, wd: float,
    rows: Optional[int], cols: Optional[int],
):
    c1 = c_ref[0]
    c2 = c_ref[1]
    ok = c_ref[2] > 0
    g = g_ref[0].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)
    m = m_ref[0]
    v = v_ref[0]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    m_out[0] = jnp.where(ok, m_new, m)
    v_out[0] = jnp.where(ok, v_new, v)
    r = (m_new * c1) / (jnp.sqrt(v_new * c2) + eps)
    u = r + wd * x

    # the (8, tc) sum blocks stay resident across a layer's tiles
    @pl.when(_first_tile())
    def init():
        xsq_out[...] = jnp.zeros_like(xsq_out)
        usq_out[...] = jnp.zeros_like(usq_out)

    xsq_out[0] += _sq_sum(x, rows, cols)
    usq_out[0] += _sq_sum(u, rows, cols)


def _apply_kernel(
    c_ref, ratio_ref, x_ref, m_ref, v_ref, x_out, dsq_out,
    *, eps: float, wd: float, lr: float,
    rows: Optional[int], cols: Optional[int],
):
    c1 = c_ref[0]
    c2 = c_ref[1]
    x = x_ref[0].astype(jnp.float32)
    r = (m_ref[0] * c1) / (jnp.sqrt(v_ref[0] * c2) + eps)
    u = r + wd * x
    ratio = ratio_ref[pl.program_id(0)]
    x_new = (x - lr * ratio * u).astype(x_out.dtype)
    x_new = jnp.where(c_ref[2] > 0, x_new, x_ref[0])
    x_out[0] = x_new

    @pl.when(_first_tile())
    def init():
        dsq_out[...] = jnp.zeros_like(dsq_out)

    dsq_out[0] += _sq_sum(x_new.astype(jnp.float32) - x, rows, cols)


def lamb_update(x, g, m, v, step, lr_t=None, ok=None, **options):
    """Fused LAMB step on one tensor.  Returns (x', m', v'[, ratio], dsq).

    ``step`` is the 1-based iteration (traced scalar); ``lr_t`` an optional
    traced LR (schedules) that multiplies ``lr``; ``ok`` an optional traced
    bool, the non-finite guard: when False every output is its input,
    bit-identical.  ``options`` are the static settings of
    :func:`_lamb_update`: betas, eps, weight decay, ``lr``, trust-ratio
    bounds, ``layer_axis`` (0 or None: stacks put layers first by
    convention), ``block``, ``interpret``.  ``return_ratio=True`` appends
    the applied per-layer trust ratio — the exact phi(‖x‖)/‖u‖ the kernel
    scaled by, *before* the lr fold-in — (shape ``(layers,)``; the
    telemetry recorder's aux).  ``dsq`` is ‖x' − x‖² over the whole leaf (a
    scalar, float32), summed as x' is written over x.

    x', m' and v' are written over x, m and v: where the caller donates
    them nothing is copied, elsewhere XLA copies the inputs first.

    The kernel works on the whole leaf, so it takes replicated leaves only
    (``ops.pallas_spec_ok``); under a sharding context every shard runs the
    same update (``sharding.context.batch_local``).
    """
    lr_t = jnp.ones((), jnp.float32) if lr_t is None else lr_t
    ok = jnp.ones((), jnp.bool_) if ok is None else ok
    return batch_local(functools.partial(_lamb_update, **options),
                       shared=(x, g, m, v, step, lr_t, ok))


@functools.partial(
    jax.jit,
    static_argnames=(
        "b1", "b2", "eps", "weight_decay", "lr", "phi_lo", "phi_hi",
        "layer_axis", "block", "interpret", "apply_trust", "return_ratio",
    ),
)
def _lamb_update(
    x: jnp.ndarray,
    g: jnp.ndarray,
    m: jnp.ndarray,
    v: jnp.ndarray,
    step: jnp.ndarray,
    lr_t: jnp.ndarray,  # traced LR (schedules); multiplies `lr`
    ok: jnp.ndarray,    # traced guard: False writes the inputs back
    *,
    lr: float = 1.0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    phi_lo: Optional[float] = None,
    phi_hi: Optional[float] = None,
    layer_axis: Optional[int] = None,
    apply_trust: bool = True,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
    return_ratio: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    if layer_axis not in (None, -1, 0):
        raise ValueError("lamb_update supports layer_axis in {None, 0}")
    stacked = layer_axis == 0
    layers = x.shape[0] if stacked else 1
    dims = x.shape[1:] if stacked else x.shape
    dims = dims or (1,)
    order = held_order(dims)
    lanes = dims[order[-1]]
    rows = math.prod(dims) // lanes
    perm = (0,) + tuple(i + 1 for i in order)
    held = tuple(((layers,) + dims)[i] for i in perm)

    def to_view(a):
        a = a.reshape((layers,) + dims)
        return jnp.transpose(a, perm).reshape(layers, rows, lanes)

    def from_view(a):
        return jnp.transpose(a.reshape(held), np.argsort(perm)).reshape(
            x.shape)

    # a tile of at least ROW_ALIGN rows within `block` (lanes pad to 128)
    tc = lanes
    if pl.cdiv(lanes, LANES) * LANES * ROW_ALIGN > block:
        tc = max(block // ROW_ALIGN // LANES, 1) * LANES
    tr = max(block // (pl.cdiv(tc, LANES) * LANES) // ROW_ALIGN, 1) * ROW_ALIGN
    tr = min(tr, pl.cdiv(rows, ROW_ALIGN) * ROW_ALIGN)
    grid = (layers, pl.cdiv(rows, tr), pl.cdiv(lanes, tc))
    ragged = dict(rows=rows if rows % tr else None,
                  cols=lanes if lanes % tc else None)

    t = step.astype(jnp.float32)
    c = jnp.stack([1.0 / (1.0 - b1**t), 1.0 / (1.0 - b2**t),
                   ok.astype(jnp.float32)])

    tile = pl.BlockSpec((1, tr, tc), lambda l, i, j: (l, i, j))
    sums = pl.BlockSpec((1, SUBLANES, tc), lambda l, i, j: (l, 0, 0))
    sums_shape = jax.ShapeDtypeStruct((layers, SUBLANES, tc), jnp.float32)
    view_shape = (layers, rows, lanes)

    xv = to_view(x)
    m_new, v_new, xsq, usq = pl.pallas_call(
        functools.partial(_moments_kernel, b1=b1, b2=b2, eps=eps,
                          wd=weight_decay, **ragged),
        grid=grid,
        in_specs=[_SMEM, tile, tile, tile, tile],
        out_specs=[tile, tile, sums, sums],
        out_shape=[
            jax.ShapeDtypeStruct(view_shape, jnp.float32),
            jax.ShapeDtypeStruct(view_shape, jnp.float32),
            sums_shape,
            sums_shape,
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        name="lamb_moments",
    )(c, xv, to_view(g), to_view(m.astype(jnp.float32)),
      to_view(v.astype(jnp.float32)))

    w_norm = jnp.sqrt(jnp.sum(xsq, axis=(1, 2)))
    u_norm = jnp.sqrt(jnp.sum(usq, axis=(1, 2)))
    if phi_lo is not None or phi_hi is not None:
        w_norm = jnp.clip(
            w_norm,
            phi_lo if phi_lo is not None else 0.0,
            phi_hi if phi_hi is not None else jnp.inf,
        )
    ratio = jnp.where(w_norm > 0, jnp.where(u_norm > 0, w_norm / u_norm, 1.0), 1.0)
    if not apply_trust:
        ratio = jnp.ones_like(ratio)
    trust = ratio  # pre-lr applied ratio (telemetry aux)
    ratio = ratio * lr_t.astype(jnp.float32)

    x_new, dsq = pl.pallas_call(
        functools.partial(_apply_kernel, eps=eps, wd=weight_decay, lr=lr,
                          **ragged),
        grid=grid,
        in_specs=[_SMEM, _SMEM, tile, tile, tile],
        out_specs=[tile, sums],
        out_shape=[jax.ShapeDtypeStruct(view_shape, x.dtype), sums_shape],
        input_output_aliases={2: 0},
        interpret=interpret,
        name="lamb_apply",
    )(c, ratio, xv, m_new, v_new)

    out = (from_view(x_new), from_view(m_new), from_view(v_new))
    if return_ratio:
        out += (trust if stacked else jnp.squeeze(trust),)
    return out + (jnp.sum(dsq),)
