"""jit'd wrappers around the Pallas kernels + optimizer/model integration.

``fused_lamb`` is a drop-in GradientTransformation equivalent to
``repro.core.lamb`` (tested for exact agreement) but whose per-leaf update is
a *fused* LAMB step — the beyond-paper bandwidth optimization for the
optimizer step (§Perf).  Two backends share one semantics:

  * ``pallas``    — the two-pass Pallas TPU kernel (≈10 N HBM traffic vs
                    ≈21 N for the unfused transform chain);
  * ``xla``       — a single fused jnp expression per leaf
                    (``kernels.ref.lamb_update_ref``) that XLA fuses into few
                    passes — the portable fallback for CPU/GPU where Pallas
                    would run in (slow) interpret mode;
  * ``interpret`` — the Pallas kernel in interpret mode (tests only);
  * ``auto``      — ``pallas`` on TPU, ``xla`` elsewhere.

``flash_sdpa`` adapts the differentiable flash-attention kernel to the
model layout (B, S, H, D) for the train/prefill paths: GQA is folded into
the kernel index maps (no materialized K/V repeat), ragged sequence
lengths are padded to the block multiple and masked via the kernel's
valid-length path, and ``resolve_flash_backend`` picks Pallas on TPU vs
the chunked-XLA scan elsewhere (same backend scheme as ``fused_lamb``).
"""
from __future__ import annotations

import re
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec

from repro.kernels.flash_attention import flash_attention
from repro.kernels.lamb_update import lamb_update
from repro.kernels.ref import lamb_update_ref
from repro.optim.base import (
    GradientTransformation,
    ScalarOrSchedule,
    clip_tree_by_global_norm,
)


def kernel_calls(hlo: str, name: str) -> List[str]:
    """Result types of the ``tpu_custom_call``s of the Pallas kernel
    ``name`` in compiled TPU HLO text (``compiled.as_text()``).

    Every ``pallas_call`` in this package carries a stable ``name``
    (``flash_fwd``, ``fused_ce_dw``, ``lamb_apply``, ...), which shows up
    in the custom call's op name; an empty list means the kernel is not in
    the program.
    """
    calls = []
    for line in hlo.splitlines():
        if ('custom_call_target="tpu_custom_call"' in line
                and f"/{name}/pallas_call" in line):
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) custom-call\(", line)
            calls.append(m.group(1) if m else line)
    return calls


def pallas_spec_ok(spec) -> bool:
    """True if a parameter with this PartitionSpec can feed the Pallas kernel.

    The fused kernel works on a ``(layers, rows, C)`` view of the whole
    leaf — valid only for replicated leaves.  A leaf
    sharded on any mesh axis (FSDP ``embed``, TP ``heads``/``ff``) must take
    the fused-XLA ``lamb_update_ref`` path instead, where GSPMD inserts the
    collectives that keep the per-layer ‖x‖/‖u‖ trust-ratio reductions
    *global* across shards.  ``None`` (no spec known) is treated as
    replicated.
    """
    return spec is None or all(e is None for e in spec)


class FusedLambState(NamedTuple):
    """Fused-LAMB optimizer state.

    ``count`` ages the moments (bias correction) and must carry across
    mixed-batch stage switches; ``sched_count`` drives LR schedules and is
    what stage-2 re-warm-up resets (mirrors the split between
    ScaleByAdamState.count and ScheduleState.count in the unfused chain).
    """

    count: jnp.ndarray
    sched_count: jnp.ndarray
    mu: Any
    nu: Any


def fused_lamb_init(params) -> FusedLambState:
    """Zero moments (always fp32 — mixed-precision masters) + zero counters."""
    zeros = lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    return FusedLambState(
        jnp.zeros([], jnp.int32), jnp.zeros([], jnp.int32), zeros(), zeros()
    )


def fused_lamb_apply(
    params: Any,
    grads: Any,
    mu: Any,
    nu: Any,
    count: jnp.ndarray,
    lr_t: jnp.ndarray,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    wd_mask: Optional[Any] = None,
    trust_mask: Optional[Any] = None,
    layer_axes: Optional[Any] = None,
    phi_bounds: Optional[Tuple[float, float]] = None,
    mode: str = "xla",
    param_specs: Optional[Any] = None,
    with_aux: bool = False,
    ok: Optional[jnp.ndarray] = None,
) -> Tuple[Any, ...]:
    """One fused LAMB step over a whole pytree:
    (params', mu', nu', update_norm).

    ``update_norm`` is ‖params' − params‖ over every leaf (float32), the
    step's ``update_norm`` metric; the kernel sums it as it writes params'
    over params, where nothing could read the old weights afterwards.

    ``ok`` (scalar bool, optional) is the non-finite guard: when False the
    computed update is discarded leaf-by-leaf — params and both moments
    come back bit-identical (the kernels write their inputs back; the XLA
    form where-selects them in the same fused expression), so a skipped
    step costs no extra memory traffic.  The caller gates the counters
    (see :func:`make_fused_lamb_step`).

    ``with_aux=True`` appends a fifth output: a pytree shaped like
    ``params`` of the *applied* per-layer trust ratios (each backend's
    ``return_ratio`` aux — the telemetry recorder's source of truth, no
    recompute from deltas).

    ``count`` is the 1-based step for bias correction and ``lr_t`` the traced
    learning rate; ``mode`` is a *resolved* backend ("pallas" | "xla" |
    "interpret").  ``param_specs`` (a PartitionSpec tree from
    ``sharding.specs_for``) makes the choice sharding-aware: on the pallas
    backend, leaves whose sharding crosses the kernel's single-device block
    layout fall back per-leaf to the fused-XLA path, whose norm reductions
    GSPMD keeps globally correct (see :func:`pallas_spec_ok`).  This is the
    direct-apply core the jit'd train step calls — no parameter-delta
    round-trip — and also what the ``fused_lamb`` GradientTransformation
    wraps for drop-in composition with the optim API.  Invariant: identical
    math to ``core.lamb`` per layer (parity-tested).
    """
    la = layer_axes
    if la is None:
        la = jax.tree.map(lambda _: -1, grads)
    else:
        la = jax.tree.map(
            lambda a: -1 if a is None else a, la,
            is_leaf=lambda x: x is None or isinstance(x, int),
        )
    wm = wd_mask if wd_mask is not None else jax.tree.map(lambda _: True, grads)
    tm = trust_mask if trust_mask is not None else jax.tree.map(lambda _: True, grads)

    treedef = jax.tree_util.tree_structure(grads)
    p_l, g_l = jax.tree.leaves(params), jax.tree.leaves(grads)
    m_l, v_l = jax.tree.leaves(mu), jax.tree.leaves(nu)
    la_l, wm_l, tm_l = jax.tree.leaves(la), jax.tree.leaves(wm), jax.tree.leaves(tm)
    if param_specs is None:
        sp_l = [None] * len(p_l)
    else:
        sp_l = jax.tree.leaves(
            param_specs,
            is_leaf=lambda s: s is None or isinstance(s, PartitionSpec),
        )

    xs, ms, vs, rs, usq = [], [], [], [], []
    for p, g, m, v, axis, wd_on, tr_on, spec in zip(
        p_l, g_l, m_l, v_l, la_l, wm_l, tm_l, sp_l
    ):
        axis = 0 if axis == 0 else None
        leaf_mode = mode
        if mode != "xla" and not pallas_spec_ok(spec):
            # sharded leaf: the kernel path (pallas AND its interpret mode)
            # assumes a single-device block layout; fall back to the fused
            # XLA expression where GSPMD keeps norm reductions global
            leaf_mode = "xla"
        if leaf_mode == "xla":
            out = lamb_update_ref(
                p, g, m, v, lr=lr_t, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay if wd_on else 0.0,
                step=count, phi_bounds=phi_bounds,
                layer_axis=axis, apply_trust=bool(tr_on),
                return_ratio=with_aux,
            )
            x_new, m_new, v_new = out[:3]
            if ok is not None:
                x_new = jnp.where(ok, x_new, p)
                m_new = jnp.where(ok, m_new, m)
                v_new = jnp.where(ok, v_new, v)
            usq.append(jnp.sum(jnp.square(
                x_new.astype(jnp.float32) - p.astype(jnp.float32))))
        else:
            out = lamb_update(
                p, g, m, v, count, lr_t, ok,
                lr=1.0, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay if wd_on else 0.0,
                phi_lo=None if phi_bounds is None else phi_bounds[0],
                phi_hi=None if phi_bounds is None else phi_bounds[1],
                layer_axis=axis, apply_trust=bool(tr_on),
                interpret=leaf_mode == "interpret",
                return_ratio=with_aux,
            )
            x_new, m_new, v_new = out[:3]
            usq.append(out[-1])
        xs.append(x_new)
        ms.append(m_new)
        vs.append(v_new)
        if with_aux:
            rs.append(out[3])

    unflat = jax.tree_util.tree_unflatten
    result = (unflat(treedef, xs), unflat(treedef, ms), unflat(treedef, vs),
              jnp.sqrt(jnp.sum(jnp.stack(usq))))
    if with_aux:
        result += (unflat(treedef, rs),)
    return result


def resolve_fused_backend(backend: str = "auto") -> str:
    """Map ``auto`` to the fastest correct backend for the current platform.

    Invariant: the returned backend is runnable here — ``pallas`` only comes
    back when the default JAX backend is a TPU.
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla", "interpret"):
        raise ValueError(f"unknown fused backend {backend!r}")
    return backend


def make_fused_lamb_step(
    learning_rate: ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    *,
    wd_mask: Optional[Any] = None,
    trust_mask: Optional[Any] = None,
    layer_axes: Optional[Any] = None,
    phi_bounds: Optional[Tuple[float, float]] = None,
    grad_clip_norm: Optional[float] = None,
    mode: str = "xla",
    param_specs: Optional[Any] = None,
    with_aux: bool = False,
):
    """The single stateful fused-LAMB core shared by the transform wrapper
    and the jit'd train step's direct path.

    Returns ``step(params, grads, state) -> (new_params, new_state,
    update_norm)``: clip → count/sched_count advance → lr(sched_count) →
    fused apply, in that order; ``update_norm`` is ‖new_params − params‖.
    ``param_specs`` propagates the per-leaf sharded-parameter fallback
    (see :func:`fused_lamb_apply`).  With ``with_aux`` the step appends
    ``trust_ratios`` — the applied per-layer ratios threaded out for the
    telemetry recorder.  ``ok``
    (scalar bool) is the train step's non-finite guard: when False the
    apply returns everything as it was and *neither counter advances* —
    the skipped step leaves the schedule position untouched.
    Invariant: keeping this sequence in one place is what guarantees
    fused-direct vs transform parity.
    """

    def step(params, grads, state: FusedLambState, ok=None):
        if grad_clip_norm is not None:
            grads = clip_tree_by_global_norm(grads, grad_clip_norm)
        adv = 1 if ok is None else ok.astype(state.count.dtype)
        count = state.count + adv
        lr_t = (
            learning_rate(state.sched_count)
            if callable(learning_rate)
            else jnp.asarray(learning_rate)
        )
        out = fused_lamb_apply(
            params, grads, state.mu, state.nu, count, lr_t,
            b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
            wd_mask=wd_mask, trust_mask=trust_mask, layer_axes=layer_axes,
            phi_bounds=phi_bounds, mode=mode, param_specs=param_specs,
            with_aux=with_aux, ok=ok,
        )
        new_params, new_mu, new_nu = out[:3]
        new_state = FusedLambState(count, state.sched_count + adv,
                                   new_mu, new_nu)
        return (new_params, new_state) + tuple(out[3:])

    return step


def fused_lamb(
    learning_rate: ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    *,
    wd_mask: Optional[Any] = None,
    trust_mask: Optional[Any] = None,
    layer_axes: Optional[Any] = None,
    phi_bounds: Optional[Tuple[float, float]] = None,
    grad_clip_norm: Optional[float] = None,
    backend: str = "auto",
    interpret: bool = False,
    param_specs: Optional[Any] = None,
) -> GradientTransformation:
    """LAMB with a fused per-leaf update (Pallas kernel or XLA fallback).

    Args mirror :func:`repro.core.lamb` (masks/axes are the model's pytree
    metadata); ``backend`` picks the fused implementation (see module doc),
    and ``interpret=True`` is a legacy alias for ``backend="interpret"``.

    Returns a ``GradientTransformation`` whose ``update`` yields parameter
    *deltas*, so it composes with ``optim.apply_updates`` and ``optim.chain``
    exactly like the unfused chain.  (The jit'd train step bypasses the delta
    round-trip via :func:`make_fused_lamb_step`.)  Invariant: per-layer trust
    ratios match ``core.lamb`` on stacked and unstacked leaves to float
    tolerance (see tests/test_kernels.py).
    """
    mode = "interpret" if interpret else resolve_fused_backend(backend)
    step = make_fused_lamb_step(
        learning_rate, b1, b2, eps, weight_decay,
        wd_mask=wd_mask, trust_mask=trust_mask, layer_axes=layer_axes,
        phi_bounds=phi_bounds, grad_clip_norm=grad_clip_norm, mode=mode,
        param_specs=param_specs,
    )

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_lamb requires params")
        new_params, new_state, _ = step(params, grads, state)
        # Return *updates* (delta) so apply_updates composes like other opts.
        updates = jax.tree.map(
            lambda new, old: (new.astype(jnp.float32) - old.astype(jnp.float32)).astype(old.dtype),
            new_params, params,
        )
        return updates, new_state

    return GradientTransformation(fused_lamb_init, update)


def resolve_flash_backend(backend: str = "auto") -> str:
    """Map ``auto`` to the fastest correct flash backend for this platform.

    Mirrors :func:`resolve_fused_backend`: the Pallas kernels only come back
    on TPU; elsewhere the chunked-``lax.scan`` XLA implementation (same
    custom-VJP math, portable) is the default, and ``interpret`` runs the
    Pallas kernels under the interpreter (tests only — slow).
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla", "interpret"):
        raise ValueError(f"unknown flash backend {backend!r}")
    return backend


def _flash_block(n: int, block: int) -> Tuple[int, int]:
    """(block_size, pad) so that ``n + pad`` divides ``block_size``.

    Lengths already block-divisible (or short sublane-aligned lengths) pass
    through unpadded; ragged lengths are padded up to the 128-lane block —
    this is what lifts the old ``s % 128 == 0`` gate on the kernel path.
    """
    b = block if n >= block else n
    if n % b or b % 8:  # ragged or sublane-misaligned: pad to the full block
        b = block
    return b, -n % b


def flash_sdpa(
    q: jnp.ndarray,  # (B, S, H, D)  model layout
    k: jnp.ndarray,  # (B, T, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    kv_valid: Optional[jnp.ndarray] = None,  # (B,) valid kv lengths
    window: int = 0,  # sliding-window size; 0 = full attention
    interpret: bool = False,
    backend: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """Flash attention on the model's (B, S, H, D) layout, GQA folded into
    the kernel index maps (no materialized K/V repeat), differentiable.

    Ragged sequence lengths are padded to the block multiple here — pad kv
    rows are masked via the kernel's valid-length path and pad q rows are
    sliced off (their cotangents are zero, so gradients stay exact).
    """
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    if interpret:
        # interpret runs the Pallas kernels under the interpreter; an
        # explicit xla request alongside it is a contradiction, not a
        # silent override
        if backend == "xla":
            raise ValueError("interpret=True conflicts with backend='xla'")
        mode = "interpret"
    else:
        mode = resolve_flash_backend(backend)

    qt = q.transpose(0, 2, 1, 3)          # (B, H, S, D)
    kt = k.transpose(0, 2, 1, 3)          # (B, Hkv, T, D)
    vt = v.transpose(0, 2, 1, 3)

    pad_q = 0
    if mode in ("pallas", "interpret"):
        block_q, pad_q = _flash_block(s, block_q)
        block_k, pad_k = _flash_block(t, block_k)
        if (causal or window) and pad_q != pad_k:
            # asymmetric padding would shift the kernel's causal/window row
            # offset (t - s); self-attention (s == t) pads symmetrically
            raise ValueError(
                f"causal/window cross-length ({s},{t}) needs "
                "block-divisible lengths"
            )
        if pad_q:
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        if pad_k:
            kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
            vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
            if kv_valid is None:
                kv_valid = jnp.full((b,), t, jnp.int32)
    # (the xla backend masks its own kv-chunk pad; no pre-padding needed)

    o = flash_attention(
        qt, kt, vt, kv_valid, causal=causal, window=window, backend=mode,
        block_q=block_q, block_k=block_k,
    )
    if pad_q:
        o = o[:, :, :s]
    return o.transpose(0, 2, 1, 3)
