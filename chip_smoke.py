#!/usr/bin/env python3
"""Run the main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: kernels, train128,
                                     # train512, serve
    python chip_smoke.py --chips 4   # train128 under --mesh data=4 FSDP,
                                     # against the same run on one chip

Phases (weights are random, made from ``--seed``):

  kernels   each Pallas kernel (flash fwd+bwd at seq 128 and 512, the
            fused CE head fwd+bwd, fused LAMB) at bert-large widths
            against its oracle in ``kernels/ref.py``.
  train128  bert-large at its published widths (24 layers, d=1024, 16
            heads, ff 4096, vocab 30522), bf16 compute, flash attention,
            the fused CE head and fused LAMB, seq 128, a few steps through
            ``Trainer.fit`` and ``DataPipeline``.
  train512  the same at seq 512 (phase 2 of the paper's two-phase recipe).
  serve     smollm-360m at its published widths through ContinuousEngine:
            greedy requests whose tokens must equal the static Engine's
            at matching batch shapes, and, on four slots at cut depth, be
            each request's own greedy choice (see ``phase_serve``).

Each training phase compiles the step once ahead of time, fails unless
the compiled HLO holds every kernel it should run (a ``tpu_custom_call``
per Pallas kernel, found by its name), prints loss and wall time per step
(after ``block_until_ready``), and fails unless its per-step losses agree
with the plain path: the same init, batches and precision with dense
attention, the dense CE head and the unfused ``core.lamb``.  On four
chips the plain path is the same fused run on ``jax.devices()[0]`` alone.

The script needs a TPU: without one it exits non-zero before any phase.
It prints one JSON line last, ``{"ok": true, "device": {...}}``, only when
every phase passed.  Times printed here are bring-up observations, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import core  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.data import DataPipeline  # noqa: E402
from repro.kernels import (  # noqa: E402
    flash_attention,
    fused_ce,
    kernel_calls,
    lamb_update,
    pallas_spec_ok,
    resolve_ce_backend,
    resolve_flash_backend,
    resolve_fused_backend,
)
from repro.kernels.ref import (  # noqa: E402
    flash_attention_ref,
    fused_ce_ref,
    lamb_update_ref,
)
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh_from_spec  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import (  # noqa: E402
    ContinuousEngine,
    Engine,
    Request,
    RequestStatus,
    ServeRequest,
)
from repro.sharding import specs_for, use_sharding  # noqa: E402
from repro.train import Trainer  # noqa: E402

FLASH = ("flash_fwd", "flash_dq", "flash_dkv")
FUSED_CE = ("fused_ce_fwd", "fused_ce_dh", "fused_ce_dw")
LAMB = ("lamb_moments", "lamb_apply")

# Global batch at seq 128: the largest the plain reference path fits on one
# 16 GB chip.  Seq 512 (phase 2 of the recipe) runs two short steps.
BATCH_128, STEPS_128 = 32, 4
BATCH_512, STEPS_512 = 4, 2

# Per-step loss agreement with the plain path.  The random-init stack
# amplifies rounding until, by about four layers, two roundings of one
# input give unrelated hidden states, so the paths agree only as far as an
# unrelated hidden state moves the loss.  On a TPU v5e, at step 1 (same
# weights), flash and dense attention differ by 6.1e-3 at seq 512 in bf16
# and by 1.9e-3 even at fp32 with full-precision matmuls.  This check
# catches gross faults; the kernels phase is the sharp one.
LOSS_RTOL = 1e-2
# Kernel against oracle: bf16 inputs and outputs, fp32 inside.
KERNEL_RTOL = 2e-2
# LAMB is fp32 end to end; only the norm reductions' order differs.
LAMB_RTOL = 1e-3
# Serving at fp32 with full-precision matmuls: how far below its context's
# top logit a greedy token may sit.  Rounding across batch shapes there is
# orders of magnitude smaller; a token from the wrong context sits about as
# far below the top as the logits spread (about 3 at random init).
GREEDY_MARGIN = 5e-2
# Depth of the four-slot check.  A random-init stack amplifies rounding
# until, by about four layers, two roundings of one input give unrelated
# hidden states, even at fp32 (PERF.md); at two layers it stays far below
# the margin.  Slot handling does not depend on depth.
SERVE_CHECK_LAYERS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def train_run(cfg, batch: int, seq: int, steps: int, *, plain: bool,
              mesh=None, seed: int = 0) -> dict:
    """``steps`` steps of bf16 LAMB MLM through Trainer.fit; returns the
    per-step losses, grad norms and wall times, the compile seconds and
    the compiled step's HLO text."""
    if plain:
        cfg = cfg.replace(use_flash_kernel=False, use_fused_ce_head=False)
    model = build_model(cfg)
    lr = core.sqrt_scaled_lr(2.5e-3, 16, batch)
    tc = TrainConfig(optimizer="lamb", learning_rate=lr, total_steps=steps,
                     seed=seed, precision="bf16", use_fused_lamb=not plain)
    trainer = Trainer(model, tc, schedule=core.warmup_poly_decay(lr, steps, 0),
                      mesh=mesh, log_every=1, log_fn=lambda _: None)
    data = DataPipeline(cfg, batch, seq, seed=seed, mesh=mesh)
    example = next(DataPipeline(cfg, batch, seq, seed=seed, mesh=mesh))
    state = trainer.init()
    t0 = time.perf_counter()
    with use_sharding(trainer.shard_ctx):
        # the jitted step Trainer.fit runs, compiled ahead of time
        compiled = trainer._step_fn.lower(state, example).compile()
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        trainer.fit(data, 1)
        jax.block_until_ready(trainer.state)
        times.append(time.perf_counter() - t0)
    out = dict(
        losses=[h["loss/total"] for h in trainer.history],
        grad_norms=[h["grad_norm"] for h in trainer.history],
        times=times, compile_s=compile_s, hlo=compiled.as_text(),
    )
    del trainer, state, data, example, compiled
    gc.collect()
    return out


def print_run(tag: str, run: dict) -> None:
    log(f"  [{tag}] compile {run['compile_s']:.2f} s")
    for i, (loss, gn, dt) in enumerate(
            zip(run["losses"], run["grad_norms"], run["times"]), 1):
        log(f"  [{tag}] step {i}: loss {loss:.6f} grad_norm {gn:.6f} "
            f"wall {dt:.4f} s")


def compare_runs(run: dict, ref: dict) -> list:
    """Failures where ``run``'s per-step losses stray from ``ref``'s."""
    fails = []
    for i, (a, b) in enumerate(zip(run["losses"], ref["losses"]), 1):
        rel = abs(a - b) / abs(b)
        log(f"  step {i}: loss {a:.6f} vs reference {b:.6f} "
            f"(rel {rel:.2e}, tolerance {LOSS_RTOL:.0e})")
        if not (np.isfinite(a) and rel <= LOSS_RTOL):
            fails.append(f"step {i} loss {a} vs {b} (rel {rel:.2e})")
    if len(run["losses"]) != len(ref["losses"]):
        fails.append("the two runs logged different step counts")
    return fails


def phase_kernels(seed: int) -> list:
    """Each Pallas kernel at bert-large widths against its oracle in
    ``kernels/ref.py``.

    The kernels take bf16 inputs and compute in fp32; the oracles get the
    same values in fp32 at the highest matmul precision.  Errors are the
    largest absolute difference over the largest reference magnitude, of
    the outputs and of every input cotangent (for LAMB, of the update
    x' - x and the moments).
    """
    cfg = get_config("bert-large")
    log(f"== kernels: Pallas vs the oracles at {cfg.name} widths")
    batch, n_rows = 4, 640
    rng = np.random.default_rng(seed)
    h, d = cfg.n_heads, cfg.head_dim

    def arr(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def err(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    fails = []

    def check(name, got, want, tol):
        e = max(err(a, b) for a, b in zip(got, want))
        log(f"  {name}: max error {e:.2e} (tolerance {tol:.0e})")
        if not e <= tol:
            fails.append(f"{name} max error {e:.2e} > {tol:.0e}")

    def vjp(f, *args):
        out, back = jax.vjp(f, *args)
        return (out,) + back(jnp.ones_like(out))

    f32 = lambda xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
    for s in (128, 512):
        q, k, v = (arr((batch, h, s, d)) for _ in range(3))
        valid = jnp.asarray(rng.integers(s // 2, s + 1, batch), jnp.int32)
        got = jax.jit(lambda *a: vjp(lambda q, k, v: flash_attention(
            q, k, v, valid, causal=False, backend="pallas"), *a))(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: vjp(lambda q, k, v: flash_attention_ref(
                q, k, v, valid, causal=False), *a))(*f32((q, k, v)))
        check(f"flash seq {s} (o, dq, dk, dv)", got, want, KERNEL_RTOL)

    hs = arr((n_rows, cfg.d_model))
    w = arr((cfg.vocab_size, cfg.d_model), scale=0.05)
    lbl = jnp.asarray(rng.integers(0, cfg.vocab_size, n_rows), jnp.int32)
    got = jax.jit(lambda *a: vjp(lambda h, w: fused_ce(
        h, w, lbl, backend="pallas")[0], *a))(hs, w)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: vjp(
            lambda h, w: fused_ce_ref(h, w, lbl)[0], *a))(*f32((hs, w)))
    check("fused CE (nll, dh, dw)", got, want, KERNEL_RTOL)

    leaf = (cfg.n_layers, cfg.d_model, h, d)
    x, g = arr(leaf, jnp.float32), arr(leaf, jnp.float32, 1e-3)
    m, v = arr(leaf, jnp.float32, 1e-3), arr(leaf, jnp.float32, 1e-3) ** 2
    got = jax.jit(lambda *a: lamb_update(
        *a, jnp.int32(3), lr=1e-3, layer_axis=0))(x, g, m, v)
    want = jax.jit(lambda *a: lamb_update_ref(
        *a, lr=1e-3, step=3, layer_axis=0))(x, g, m, v)
    check("fused LAMB (x' - x, m', v')", (got[0] - x,) + got[1:3],
          (want[0] - x,) + want[1:], LAMB_RTOL)
    return fails


def check_kernels(hlo: str, names) -> list:
    fails = []
    for name in names:
        shapes = kernel_calls(hlo, name)
        log(f"  kernel {name}: {len(shapes)} call(s) {shapes[:1]}")
        if not shapes:
            fails.append(f"no tpu_custom_call for {name} in the step's HLO")
    return fails


def phase_train(name: str, batch: int, seq: int, steps: int,
                seed: int) -> list:
    cfg = get_config("bert-large")
    log(f"== {name}: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
        f"heads={cfg.n_heads} ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"batch={batch} seq={seq} steps={steps} bf16")
    run = train_run(cfg, batch, seq, steps, plain=False, seed=seed)
    print_run("fused", run)
    fails = check_kernels(run.pop("hlo"), FLASH + FUSED_CE + LAMB)
    ref = train_run(cfg, batch, seq, steps, plain=True, seed=seed)
    print_run("plain", ref)
    ref.pop("hlo")
    return fails + compare_runs(run, ref)


def phase_train_mesh(batch: int, seq: int, steps: int, seed: int) -> list:
    cfg = get_config("bert-large")
    mesh = make_mesh_from_spec("data=4")
    model = build_model(cfg)
    forms = [pallas_spec_ok(s) for s in jax.tree.leaves(
        specs_for(model.defs, mesh),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))]
    n_pallas = sum(forms)
    log(f"== train128 data=4: {cfg.name} batch={batch} seq={seq} "
        f"steps={steps} bf16; fused LAMB leaves: pallas={n_pallas} "
        f"xla={len(forms) - n_pallas} (FSDP-sharded leaves take XLA)")
    run = train_run(cfg, batch, seq, steps, plain=False, mesh=mesh, seed=seed)
    print_run("data=4", run)
    hlo = run.pop("hlo")
    fails = check_kernels(hlo, FLASH + FUSED_CE + (LAMB if n_pallas else ()))
    # the kernels must see the per-chip batch, not the global one
    want = batch // 4 * cfg.n_heads
    got = kernel_calls(hlo, "flash_fwd")
    if not got or f"[{want},{seq}," not in got[0]:
        fails.append(f"flash_fwd runs on {got[:1]}, not the per-chip "
                     f"batch×heads {want}")
    ref = train_run(cfg, batch, seq, steps, plain=False, seed=seed)
    print_run("one chip", ref)
    ref.pop("hlo")
    return fails + compare_runs(run, ref)


def greedy_gaps(model, params, prompts, outs) -> list:
    """Per request, the largest gap between a row's top logit and the logit
    of the token the engine chose there, from a batch-1 forward over the
    prompt and the generated tokens (teacher forcing)."""
    fwd = jax.jit(lambda p, t: model.apply(p, {"tokens": t})[0])
    gaps = []
    for prompt, out in zip(prompts, outs):
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)[None]
        logits = np.asarray(fwd(params, jnp.asarray(seq))[0], np.float32)
        logits = logits[len(prompt) - 1:]
        chosen = logits[np.arange(len(out)), out]
        gaps.append(float(np.max(logits.max(axis=-1) - chosen)))
    return gaps


def phase_serve(seed: int) -> list:
    """Greedy requests through ContinuousEngine, checked two ways.

    On the TPU, XLA's rounding at bf16 depends on the batch shape (the GQA
    attention einsum, RoPE), and a random-init 32-layer model amplifies a
    one-ulp difference into other tokens.  So:

    * bf16, as served: a one-slot ContinuousEngine (queue, admission, slot
      reuse) must give the static Engine's tokens, each request served
      alone at the same shapes.
    * four slots, with slots reused: at fp32 activations, full-precision
      matmuls and ``SERVE_CHECK_LAYERS`` layers (widths as published),
      every token must be the greedy choice for its own request's context,
      within ``GREEDY_MARGIN`` of the top logit of a batch-1 forward over
      that request alone.  A token taken from another slot's context or a
      stale KV slot lands far below the top logit.
    """
    cfg = get_config("smollm-360m")
    model = build_model(cfg)
    log(f"== serve: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size} "
        f"params={model.param_count() / 1e6:.1f}M")
    params = model.init(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    n, prompt_len, max_new, max_len = 6, 32, 16, 64
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n)]
    fails = []

    def serve(model, params, slots):
        t0 = time.perf_counter()
        out = ContinuousEngine(model, params, n_slots=slots, max_len=max_len,
                               seed=seed).generate(
            [ServeRequest(p, max_new_tokens=max_new) for p in prompts])
        log(f"  ContinuousEngine {model.cfg.activation_dtype}, {slots} "
            f"slot(s): {time.perf_counter() - t0:.2f} s (compile included)")
        toks = []
        for i, r in enumerate(out):
            toks.append([int(t) for t in r.out_tokens])
            if r.status is not RequestStatus.COMPLETED or len(toks[-1]) != max_new:
                fails.append(f"{slots} slot(s): request {i} ended "
                             f"{r.status.value} with {len(toks[-1])} tokens")
        return toks

    static = Engine(model, params, max_len=max_len)
    t0 = time.perf_counter()
    ref = [[int(t) for t in static.generate_batch(
        [Request(p, max_new_tokens=max_new)])[0].out_tokens] for p in prompts]
    log(f"  static Engine bf16, one request at a time: "
        f"{time.perf_counter() - t0:.2f} s (compile included)")
    for i, (toks, want) in enumerate(zip(serve(model, params, 1), ref)):
        log(f"    req {i}: {toks} {'==' if toks == want else '!='} static")
        if toks != want:
            fails.append(f"1 slot: request {i} tokens != static Engine's")

    model32 = build_model(cfg.replace(activation_dtype="float32",
                                      n_layers=SERVE_CHECK_LAYERS))
    params32 = model32.init(jax.random.key(seed))
    log(f"  four-slot check: {SERVE_CHECK_LAYERS} layers, fp32")
    with jax.default_matmul_precision("highest"):
        outs = serve(model32, params32, 4)
        gaps = greedy_gaps(model32, params32, prompts, outs)
    for i, (toks, gap) in enumerate(zip(outs, gaps)):
        log(f"    req {i}: {toks} largest gap to the top logit {gap:.3e} "
            f"(margin {GREEDY_MARGIN:.0e})")
        if not gap <= GREEDY_MARGIN:
            fails.append(f"4 slots: request {i} chose a token {gap:.3e} "
                         "below its top logit")
    return fails


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (platform {dev.platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devices)} "
                 "device(s) found")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    log(f"backends: flash={resolve_flash_backend()} "
        f"fused_ce={resolve_ce_backend()} fused_lamb={resolve_fused_backend()}")

    if args.chips == 4:
        phases = [("train128 data=4", lambda: phase_train_mesh(
            BATCH_128, 128, STEPS_128, args.seed))]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(args.seed)),
            ("train128", lambda: phase_train(
                "train128", BATCH_128, 128, STEPS_128, args.seed)),
            ("train512", lambda: phase_train(
                "train512", BATCH_512, 512, STEPS_512, args.seed)),
            ("serve", lambda: phase_serve(args.seed)),
        ]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        fails = phase()
        log(f"-- {name}: {'PASS' if not fails else 'FAIL'} "
            f"({time.perf_counter() - t0:.1f} s)")
        failed += [f"{name}: {f}" for f in fails]
    if failed:
        for f in failed:
            log(f"FAIL {f}")
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
