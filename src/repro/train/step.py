"""Train-step factory: the large-batch scaling path.

``make_train_step`` assembles the paper's recipe into one jit-able step:

  * **gradient accumulation** — ``lax.scan`` over ``tc.grad_accum_steps``
    microbatch slices, so ``global_batch = microbatch × accum × DP`` on fixed
    activation memory.  Each slice's mean loss/grad is weighted by its
    supervised-token count, so k microbatches reproduce the single
    full-batch token mean exactly even under MLM/HuBERT masking.
  * **mixed precision** — ``tc.precision="bf16"`` casts the fp32 master
    params to bf16 *inside* the loss (activations and matmuls run in bf16,
    gradients flow back to fp32 masters); optimizer moments and every norm
    reduction in the trust ratio stay fp32 (see core/strategy, optim/base).
  * **fused LAMB** — ``tc.use_fused_lamb`` swaps the unfused
    ``scale_by_adam → trust-ratio → -lr`` transform chain (≈21 N optimizer
    traffic) for the fused per-leaf update (Pallas kernel on TPU, single
    fused XLA expression elsewhere; ≈10 N), parity-checked per layer.
  * **fused MLM head** — ``cfg.use_fused_ce_head`` (default on for
    bert-large) makes the loss gather supervised positions before the vocab
    projection and stream the CE over vocab chunks, so no ``(B, S, V)``
    logits tensor is ever materialized (see ``make_loss_fn`` / train/loss).

Named scopes label the compiled step's ops for a profiler trace:
``cast_params`` (the bf16 cast), ``jvp(model)`` (forward),
``transpose(jvp(model))`` (backward) and ``optimizer`` (everything after
the gradients).  They change op metadata only, not the program.

``make_optimizer`` wires the model's pytree metadata (weight-decay mask,
trust-ratio mask, stacked-layer axes) into the paper's optimizers so that
LAMB's layerwise semantics survive scanned parameter stacks.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import core, nn, optim
from repro.configs.base import ModelConfig, TrainConfig
from repro.kernels import (
    fused_lamb,
    fused_lamb_init,
    make_fused_lamb_step,
    resolve_fused_backend,
)
from repro.models.api import Model
from repro.telemetry.trust import PER_LAYER_KEY
from repro.train.faults import (
    apply_grad_faults,
    apply_loss_faults,
    split_faults,
)
from repro.train.loss import check_fused_ce_supported, loss_for

# Metric key carrying each microbatch's supervised-token count (set by the
# loss functions); drives token-weighted accumulation below.
TOKEN_WEIGHT_KEY = "tokens/supervised"

# Metric key the non-finite guard reports under: 1.0 when the step was
# skipped (state passed through unchanged), 0.0 otherwise.  Only present
# with ``tc.skip_nonfinite``.
GUARD_KEY = "nonfinite/skip"

LOSS_KEY = "loss/total"


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray
    # cumulative non-finite-guard skips; persisted with the checkpoint so a
    # resume can fast-forward the data stream by step + skipped *batches*
    # (a skipped step consumed a batch without advancing ``step``)
    skipped: jnp.ndarray


def tree_all_finite(tree, *extra) -> jnp.ndarray:
    """One fused all-finite reduction over a pytree (+ extra leaves).

    Returns a scalar bool.  Under GSPMD the per-leaf ``jnp.all`` reductions
    stay *global* — sharded leaves contribute collectives, so every device
    agrees on the verdict (required: the skip select must be uniform).
    Integer leaves are finite by definition (``jnp.isfinite`` handles them).
    """
    leaves = list(jax.tree.leaves(tree)) + [x for x in extra if x is not None]
    if not leaves:
        return jnp.asarray(True)
    oks = [jnp.all(jnp.isfinite(x)) for x in leaves]
    return jnp.all(jnp.stack(oks)) if len(oks) > 1 else oks[0]


def _wants_fused(model: Model, tc: TrainConfig) -> bool:
    return bool(tc.use_fused_lamb or model.cfg.use_fused_lamb_kernel)


def _check_fused_supported(tc: TrainConfig) -> None:
    if not tc.bias_correction or tc.moment_dtype is not None:
        raise ValueError(
            "fused LAMB supports bias-corrected fp32 moments only; "
            "unset use_fused_lamb or bias_correction/moment_dtype"
        )


def make_optimizer(
    model: Model, tc: TrainConfig, schedule=None, *, param_specs=None
) -> optim.GradientTransformation:
    """Build the configured optimizer with the model's layerwise metadata.

    ``param_specs`` (a PartitionSpec tree from ``sharding.specs_for``) makes
    the fused-LAMB path sharding-aware: FSDP/TP-sharded leaves fall back
    per-leaf from the Pallas kernel to the fused-XLA update, whose
    trust-ratio norm reductions GSPMD keeps globally correct.

    Invariant: the returned transformation consumes *token-mean* fp32 grads
    and returns parameter deltas for ``optim.apply_updates``, on both the
    fused and unfused LAMB paths.
    """
    lr = schedule if schedule is not None else tc.learning_rate
    wd_mask = model.wd_mask()
    trust_mask = model.trust_mask()
    layer_axes = model.layer_axes()
    common = dict(
        wd_mask=wd_mask, trust_mask=trust_mask, layer_axes=layer_axes,
        phi_bounds=tc.phi_bounds,
    )
    name = tc.optimizer
    if name == "lamb" and _wants_fused(model, tc):
        _check_fused_supported(tc)
        return fused_lamb(
            lr, tc.b1, tc.b2, tc.eps, tc.weight_decay,
            grad_clip_norm=tc.grad_clip_norm,
            backend=tc.fused_backend, param_specs=param_specs, **common,
        )
    if name == "lamb":
        return core.lamb(
            lr, tc.b1, tc.b2, tc.eps, tc.weight_decay,
            bias_correction=tc.bias_correction,
            grad_clip_norm=tc.grad_clip_norm,
            moment_dtype=tc.moment_dtype, **common,
        )
    if name == "lans":
        return core.lans(
            lr, tc.b1, tc.b2, tc.eps, tc.weight_decay,
            bias_correction=tc.bias_correction,
            grad_clip_norm=tc.grad_clip_norm,
            moment_dtype=tc.moment_dtype, **common,
        )
    if name == "nlamb":
        return core.nlamb(lr, weight_decay=tc.weight_decay,
                          grad_clip_norm=tc.grad_clip_norm, **common)
    if name == "nnlamb":
        return core.nnlamb(lr, weight_decay=tc.weight_decay,
                           grad_clip_norm=tc.grad_clip_norm, **common)
    if name == "lars":
        return core.lars(lr, momentum=tc.b1, weight_decay=tc.weight_decay, **common)
    if name == "adam":
        return optim.adam(lr, tc.b1, tc.b2, tc.eps)
    if name == "adamw":
        return optim.adamw(lr, tc.b1, tc.b2, tc.eps, tc.weight_decay, wd_mask)
    if name == "adagrad":
        return optim.adagrad(lr)
    if name == "momentum":
        return optim.momentum(lr, tc.b1, tc.weight_decay, wd_mask)
    raise ValueError(f"unknown optimizer {name!r}")


def make_loss_fn(
    model: Model,
    compute_dtype: Optional[str] = None,
    *,
    use_fused_ce: Optional[bool] = None,
) -> Callable:
    """loss_fn(params, batch) -> (loss, metrics) for this model's family.

    ``compute_dtype`` (e.g. ``"bfloat16"``) casts params inside the loss so
    the forward/backward run in low precision while ``params`` — and hence
    the gradients that flow back through the cast — stay fp32 masters.
    (The train step instead casts once *outside* the accumulation scan and
    passes ``compute_dtype=None`` here, amortizing the cast over microbatches;
    the gradients w.r.t. the cast copy are identical either way.)

    ``use_fused_ce`` overrides ``cfg.use_fused_ce_head``: when on, the model
    returns final hidden states instead of ``(B, S, V)`` logits and the loss
    runs the fused MLM head — gather supervised positions, then chunked-vocab
    CE (``kernels/fused_ce.py``) — so the logits tensor never exists.
    """
    cfg = model.cfg
    fused_ce_head = cfg.use_fused_ce_head if use_fused_ce is None else use_fused_ce
    if fused_ce_head:
        check_fused_ce_supported(cfg)
    loss_impl = loss_for(cfg)

    def loss_fn(params, batch):
        if fused_ce_head:
            # cast once here (not inside apply) so the loss's vocab
            # projection sees the same compute-dtype copy the forward ran
            # on — otherwise the mixed-precision policy would silently not
            # apply to the fused head's matmuls
            if compute_dtype is not None:
                params = nn.cast_tree(params, jnp.dtype(compute_dtype))
            hidden, aux = model.apply(params, batch, return_hidden=True)
            return loss_impl(None, batch, aux, cfg, params=params, hidden=hidden)
        logits, aux = model.apply(params, batch, compute_dtype=compute_dtype)
        return loss_impl(logits, batch, aux, cfg, params=params)

    return loss_fn


def _microbatch_grads(loss_fn, params, batch, n_micro: int):
    """Token-weighted sequential grad accumulation over ``n_micro`` slices.

    Returns fp32 grads equal to the full-batch token-mean gradient:
    ``g = Σ_i w_i g_i / Σ_i w_i`` with ``w_i`` the slice's supervised-token
    count (uniform weights when the loss reports none).  Metrics are averaged
    with the same weights, except ``tokens/supervised`` which is summed.
    """

    for x in jax.tree.leaves(batch):
        if x.shape[0] % n_micro:
            raise ValueError(
                f"global batch {x.shape[0]} is not divisible by "
                f"accum_steps {n_micro}; remainder examples would be dropped"
            )

    def slice_batch(b, i):
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(
                x, i * (x.shape[0] // n_micro), x.shape[0] // n_micro, 0
            ),
            b,
        )

    def model_loss(p, b):
        # the scope names the forward's ops ``jvp(model)`` and the
        # backward's ``transpose(jvp(model))`` in the compiled program
        with jax.named_scope("model"):
            return loss_fn(p, b)

    def one(i):
        (loss, metrics), g = jax.value_and_grad(model_loss, has_aux=True)(
            params, slice_batch(batch, i)
        )
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        w = metrics.get(TOKEN_WEIGHT_KEY, jnp.asarray(1.0, jnp.float32))
        return g, metrics, w

    g0, m0, w0 = one(0)
    if n_micro == 1:
        return g0, m0

    def body(carry, i):
        g_acc, m_acc, w_acc = carry
        g, m, w = one(i)
        g_acc = jax.tree.map(lambda a, b: a + w * b, g_acc, g)
        m_acc = jax.tree.map(lambda a, b: a + w * b, m_acc, m)
        return (g_acc, m_acc, w_acc + w), None

    g0w = jax.tree.map(lambda x: w0 * x, g0)
    m0w = jax.tree.map(lambda x: w0 * x, m0)
    (g, m, w), _ = jax.lax.scan(body, (g0w, m0w, w0), jnp.arange(1, n_micro))
    inv = 1.0 / w
    metrics = jax.tree.map(lambda x: x * inv, m)
    if TOKEN_WEIGHT_KEY in metrics:
        metrics[TOKEN_WEIGHT_KEY] = w  # total over the global batch, not mean
    return jax.tree.map(lambda x: x * inv, g), metrics


def make_train_step(
    model: Model,
    tc: TrainConfig,
    schedule=None,
    *,
    optimizer: Optional[optim.GradientTransformation] = None,
    param_specs=None,
) -> Tuple[Callable, Callable]:
    """Returns (init_fn(rng) -> TrainState, step_fn(state, batch) -> (state, metrics)).

    ``step_fn`` consumes the *global* batch; accumulation slices it into
    ``tc.grad_accum_steps`` microbatches internally, so activation memory is
    bounded by the microbatch while optimizer semantics see the global batch.

    With ``tc.use_fused_lamb`` (and no explicit ``optimizer``), the step
    bypasses the transform chain entirely and calls the fused LAMB apply
    in-place on the fp32 masters — no parameter-delta round-trip.

    ``step_fn`` is mesh-agnostic: under a sharded launch the Trainer jits it
    with explicit ``in_shardings``/``out_shardings`` (see
    ``sharding.train_state_shardings``), and ``param_specs`` carries the
    parameter PartitionSpecs into the fused-LAMB per-leaf backend choice.
    """
    fused_direct = (
        optimizer is None and tc.optimizer == "lamb" and _wants_fused(model, tc)
    )
    loss_fn = make_loss_fn(model)  # cast hoisted into step_fn, see below
    n_micro = tc.grad_accum_steps
    compute_dtype = tc.compute_dtype

    def cast_params(params):
        if compute_dtype is None:
            return params
        with jax.named_scope("cast_params"):
            return nn.cast_tree(params, jnp.dtype(compute_dtype))

    guard = tc.skip_nonfinite

    def grads_and_metrics(params, batch):
        # fault channels (tests/harness) ride the batch as fault/* leaves;
        # pop them before the loss sees the batch, apply to the grads after
        # accumulation — so a poisoned gradient looks exactly like a real
        # non-finite microbatch to the guard below
        batch, faults = split_faults(batch)
        grads, metrics = _microbatch_grads(
            loss_fn, cast_params(params), batch, n_micro
        )
        grads = apply_grad_faults(grads, faults)
        metrics = apply_loss_faults(dict(metrics), faults)
        metrics["grad_norm"] = _global_norm(grads)
        return grads, metrics

    def with_gradients(update):
        """step_fn(state, batch): the gradients, then ``update(state, grads,
        metrics)`` under the ``optimizer`` scope, so that every op after
        the gradients (the update, the finite guard, ``update_norm``, the
        trust diagnostics) carries it in the compiled program."""

        def step_fn(state: TrainState, batch) -> Tuple[TrainState, Dict]:
            grads, metrics = grads_and_metrics(state.params, batch)
            with jax.named_scope("optimizer"):
                return update(state, grads, metrics)

        return step_fn

    def finite_guard(grads, metrics):
        """Scalar ok-flag: everything the update would consume is finite."""
        return tree_all_finite(grads, metrics.get(LOSS_KEY))

    def trust_diag(params, updates):
        return core.summarize_trust_ratios(
            core.trust_ratio_tree(
                params, updates, layer_axes=model.layer_axes(),
                phi_bounds=tc.phi_bounds,
            )
        )

    # per-layer telemetry recording (off by default): the records stay on
    # device inside the metrics pytree — no host sync until the Trainer's
    # log-step fetch pops PER_LAYER_KEY
    record = tc.record_trust_ratios

    def per_layer_records(params, updates, applied_ratio=None):
        return core.trust_records(
            params, updates, layer_axes=model.layer_axes(),
            phi_bounds=tc.phi_bounds, trust_ratio=applied_ratio,
        )

    if fused_direct:
        _check_fused_supported(tc)
        fused_step = make_fused_lamb_step(
            schedule if schedule is not None else tc.learning_rate,
            tc.b1, tc.b2, tc.eps, tc.weight_decay,
            wd_mask=model.wd_mask(), trust_mask=model.trust_mask(),
            layer_axes=model.layer_axes(), phi_bounds=tc.phi_bounds,
            grad_clip_norm=tc.grad_clip_norm,
            mode=resolve_fused_backend(tc.fused_backend),
            param_specs=param_specs,
            with_aux=record,
        )

        def init_fn(rng) -> TrainState:
            params = model.init(rng)
            return TrainState(
                params, fused_lamb_init(params), jnp.zeros([], jnp.int32),
                jnp.zeros([], jnp.int32),
            )

        def update(state: TrainState, grads, metrics):
            if guard:
                # the guard threads through the fused apply: every leaf
                # where-selects old vs new in the same fused expression and
                # the moment/schedule counters advance by ok, so a skipped
                # step leaves the entire opt state bit-identical
                ok = finite_guard(grads, metrics)
                out = fused_step(state.params, grads, state.opt_state, ok=ok)
            else:
                out = fused_step(state.params, grads, state.opt_state)
            params, opt_state = out[0], out[1]
            # same metric schema as the unfused path; the kernels sum
            # ‖params' − params‖ as they write params' over params
            metrics["update_norm"] = out[2]
            if tc.log_trust_ratios or record:
                updates = jax.tree.map(
                    lambda new, old: new.astype(jnp.float32)
                    - old.astype(jnp.float32),
                    params, state.params,
                )
                if tc.log_trust_ratios:
                    metrics.update(trust_diag(state.params, updates))
                if record:
                    # out[3] = the kernels' applied per-layer ratios (aux)
                    metrics[PER_LAYER_KEY] = per_layer_records(
                        state.params, updates, applied_ratio=out[3]
                    )
            if guard:
                adv = ok.astype(jnp.int32)
                metrics[GUARD_KEY] = 1.0 - adv.astype(jnp.float32)
                new_state = TrainState(
                    params, opt_state, state.step + adv,
                    state.skipped + (1 - adv),
                )
            else:
                new_state = TrainState(
                    params, opt_state, state.step + 1, state.skipped
                )
            return new_state, metrics

        return init_fn, with_gradients(update)

    opt = (
        optimizer
        if optimizer is not None
        else make_optimizer(model, tc, schedule, param_specs=param_specs)
    )

    def init_fn(rng) -> TrainState:
        params = model.init(rng)
        return TrainState(params, opt.init(params), jnp.zeros([], jnp.int32),
                          jnp.zeros([], jnp.int32))

    def update(state: TrainState, grads, metrics):
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optim.apply_updates(state.params, updates)
        if guard:
            # tree.map(where) select at the TrainState level: a non-finite
            # step passes params AND the whole transform-chain state through
            # unchanged — schedule counters included, since ScheduleState
            # lives inside opt_state
            ok = finite_guard(grads, metrics)
            keep = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
            params = jax.tree.map(keep, params, state.params)
            opt_state = jax.tree.map(keep, opt_state, state.opt_state)
            adv = ok.astype(jnp.int32)
            metrics["update_norm"] = jnp.where(ok, _global_norm(updates), 0.0)
            metrics[GUARD_KEY] = 1.0 - adv.astype(jnp.float32)
        else:
            metrics["update_norm"] = _global_norm(updates)
        if tc.log_trust_ratios:
            metrics.update(trust_diag(state.params, updates))
        if record:
            # transform chains don't expose their internal ratio; record the
            # post-hoc phi(||x||)/||Δx|| diagnostic (same semantics as
            # trust_diag, per layer instead of summarized)
            metrics[PER_LAYER_KEY] = per_layer_records(state.params, updates)
        if guard:
            new_state = TrainState(params, opt_state, state.step + adv,
                                   state.skipped + (1 - adv))
        else:
            new_state = TrainState(params, opt_state, state.step + 1,
                                   state.skipped)
        return new_state, metrics

    return init_fn, with_gradients(update)


def _global_norm(tree):
    sq = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(sq)))
