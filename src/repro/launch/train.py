"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 --batch 16 --seq 128 --optimizer lamb [--smoke] \
        [--mixed-batch] [--checkpoint-dir ckpt/] [--checkpoint-every 50] \
        [--async-checkpoint] [--resume] [--mesh data=8,model=1] \
        [--accum-steps 4] [--precision bf16] [--fused-lamb] [--fused-ce] \
        [--telemetry-dir runs/x] [--log-trust-ratios] \
        [--skip-nonfinite] [--rollback-on-spike --spike-window 32 \
         --max-rollbacks 3] [--preempt-grace 30]

``--checkpoint-dir`` + ``--checkpoint-every`` persist the full train state
(params, LAMB moments, step).  ``--async-checkpoint`` makes saves
double-buffered and non-blocking (disk writes overlap training;
``checkpoint`` telemetry events carry the timings), and ``--resume``
continues a killed run from the latest complete checkpoint — bit-exact
against a run that was never interrupted (docs/reliability.md).

``--telemetry-dir`` turns on the unified telemetry subsystem: a structured
JSONL event log (run provenance, per-interval step events, span timings,
checkpoints) plus a ``RUN_REPORT.json`` aggregate written at exit.  Combined
with ``--log-trust-ratios`` it also records LAMB's per-layer trust ratios
and update/param norms each logged step (App. H-style diagnostics).  Without
the flag every telemetry hook is a null sink — the step function and metrics
history are bit-identical to a run without telemetry.

``--fused-ce`` (default on for bert-large) runs the MLM head fused:
supervised positions are gathered before the vocab projection and the CE
streams over vocab chunks, so the ``(B, S, V)`` logits tensor never
exists (``--no-fused-ce`` restores the dense head).

``--batch`` is the *global* batch; ``--accum-steps k`` runs it as k
sequential microbatches of ``batch/k`` (activation memory scales with the
microbatch, optimizer semantics with the global batch — the paper's
batch-to-the-hardware-limit recipe on fixed memory).  ``--precision bf16``
computes forward/backward in bf16 against fp32 master params, and
``--fused-lamb`` routes the optimizer through the fused update kernel
(Pallas on TPU, fused XLA elsewhere).

``--mesh data=N,model=M`` runs the step truly sharded: params and LAMB
moments FSDP-sharded over ``data`` (TP over ``model``), batches split over
``data``, explicit in/out shardings on the jit'd step (see
docs/sharding.md).  With no ``--mesh``, multi-device hosts default to
``data=<all devices>`` (``--model-parallel`` is the legacy spelling for
the model axis).

Robustness (docs/reliability.md): ``--skip-nonfinite`` arms the in-jit
non-finite guard (NaN/Inf in loss or grads skips the update in-graph);
``--rollback-on-spike`` arms the loss-spike watchdog, which restores the
last *validated* checkpoint on a trip and aborts with exit code 3 after
``--max-rollbacks``; ``--preempt-grace N`` turns SIGTERM/SIGINT into a
final checkpoint + clean ``status=preempted`` exit, resumable bit-exact
with ``--resume``.

``--optimizer`` picks the update rule: ``lamb`` (Algorithm 2, default),
``lans`` (Zheng et al.'s 54-minute variant — block-normalized gradients
into the Adam moments plus a Nesterov two-term update, each term
trust-rescaled per layer; see core/lans.py), ``nlamb``/``nnlamb`` (App. D),
``lars``, and the tuned baselines ``adam``/``adamw``/``adagrad``/
``momentum``.  All of them run through the same accumulation / precision /
sharding path; ``--fused-lamb`` applies to LAMB only.

``--smoke`` swaps in the reduced config of the same family (CPU-runnable);
the full configs are exercised via the dry-run (repro.launch.dryrun).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import jax

from repro import core
from repro.configs import get_config, smoke_config
from repro.launch.cache import enable_compile_cache
from repro.configs.base import TrainConfig
from repro.core.mixed_batch import make_stage
from repro.data import DataPipeline
from repro.launch.mesh import make_host_mesh, make_mesh_from_spec
from repro.models import build_model
from repro.telemetry import EventLog, RunReport
from repro.train import DivergenceError, SupervisorConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="lamb")
    ap.add_argument("--base-lr", type=float, default=2.5e-3)
    ap.add_argument("--base-batch", type=int, default=16)
    ap.add_argument("--warmup-ratio", type=float, default=1 / 40)
    ap.add_argument("--weight-decay", type=float, default=0.01)
    ap.add_argument("--mixed-batch", action="store_true",
                    help="two-stage §4.1 recipe (seq -> 4*seq, batch -> batch/4)")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--precision", default="fp32", choices=["fp32", "bf16"],
                    help="compute dtype (bf16 keeps fp32 master params)")
    ap.add_argument("--fused-lamb", action="store_true",
                    help="fused LAMB update (Pallas on TPU, XLA fallback)")
    ap.add_argument("--flash", dest="flash", action="store_true", default=None,
                    help="force flash attention on (Pallas fwd+bwd kernels "
                         "on TPU, chunked XLA elsewhere)")
    ap.add_argument("--no-flash", dest="flash", action="store_false",
                    help="force the dense attention path")
    ap.add_argument("--fused-ce", dest="fused_ce", action="store_true",
                    default=None,
                    help="force the fused MLM head on (supervised-position "
                         "gather + chunked-vocab CE; no (B,S,V) logits — "
                         "default on for bert-large)")
    ap.add_argument("--no-fused-ce", dest="fused_ce", action="store_false",
                    help="force the dense logits + log_softmax head")
    ap.add_argument("--log-trust-ratios", action="store_true",
                    help="per-step trust-ratio min/mean/max in history; with "
                         "--telemetry-dir, also the per-layer recorder "
                         "(trust_ratios events + histogram in the report)")
    ap.add_argument("--telemetry-dir", default="",
                    help="write a structured event log (events.jsonl) and a "
                         "RUN_REPORT.json aggregate here; off = null sink "
                         "(zero overhead)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--async-checkpoint", action="store_true",
                    help="double-buffered background saves: the step loop "
                         "pays only the device->host snapshot, the disk "
                         "write overlaps training (checkpoint telemetry "
                         "events carry snapshot/blocked/write timings)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest complete checkpoint in "
                         "--checkpoint-dir (full train state: params, "
                         "optimizer moments, step) and continue to --steps; "
                         "the data pipeline is fast-forwarded so the "
                         "continuation matches an uninterrupted run")
    ap.add_argument("--skip-nonfinite", action="store_true",
                    help="in-jit non-finite guard: any NaN/Inf in the loss "
                         "or gradients skips the optimizer update (state "
                         "passes through unchanged, schedule counters hold) "
                         "and counts the step in TrainState.skipped")
    ap.add_argument("--rollback-on-spike", action="store_true",
                    help="loss-spike watchdog: robust (median+MAD) z-score "
                         "over a trailing window; a trip restores the last "
                         "validated checkpoint and fast-forwards the data "
                         "stream past the suspect batches (requires "
                         "--checkpoint-dir + --checkpoint-every)")
    ap.add_argument("--spike-window", type=int, default=32,
                    help="trailing-loss window size for the spike detector")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="rollback budget; exceeding it aborts with a "
                         "divergence diagnostic (exit code 3)")
    ap.add_argument("--preempt-grace", type=float, default=None,
                    help="seconds: install a SIGTERM/SIGINT handler that "
                         "finishes the current step, writes a final "
                         "checkpoint (bounded by this grace window) and "
                         "exits cleanly with status=preempted")
    ap.add_argument("--mesh", default="",
                    help="mesh axes, e.g. data=8,model=1 (uses the first "
                         "prod(sizes) local devices); params + LAMB moments "
                         "are FSDP-sharded over data, TP over model")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="legacy spelling: model-axis size of the host mesh "
                         "(ignored when --mesh is given)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.accum_steps < 1:
        raise SystemExit(f"--accum-steps must be >= 1, got {args.accum_steps}")
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    if args.rollback_on_spike and not (
        args.checkpoint_dir and args.checkpoint_every
    ):
        raise SystemExit(
            "--rollback-on-spike requires --checkpoint-dir and "
            "--checkpoint-every (rollback needs a checkpoint to restore)"
        )
    if args.rollback_on_spike and args.mixed_batch:
        raise SystemExit("--rollback-on-spike is not supported with "
                         "--mixed-batch")
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.flash is not None:
        cfg = cfg.replace(use_flash_kernel=args.flash)
    if args.fused_ce is not None:
        cfg = cfg.replace(use_fused_ce_head=args.fused_ce)
    model = build_model(cfg)
    print(f"arch={cfg.name} params={model.param_count()/1e6:.1f}M "
          f"active={model.active_param_count()/1e6:.1f}M")
    print(f"global_batch={args.batch} "
          f"microbatch={args.batch // args.accum_steps} "
          f"accum={args.accum_steps} precision={args.precision} "
          f"fused_lamb={args.fused_lamb} flash={cfg.use_flash_kernel} "
          f"fused_ce={cfg.use_fused_ce_head}")

    mesh = None
    if args.mesh:
        mesh = make_mesh_from_spec(args.mesh)
    elif args.model_parallel > 1 or len(jax.devices()) > 1:
        mesh = make_host_mesh(args.model_parallel)
    if mesh is not None:
        print(f"mesh={dict(mesh.shape)} devices={mesh.devices.size}")

    lr = core.sqrt_scaled_lr(args.base_lr, args.base_batch, args.batch)
    warmup_ratio = core.linear_epoch_warmup_ratio(
        args.warmup_ratio, args.base_batch, args.batch
    )
    if args.batch % args.accum_steps:
        raise SystemExit(
            f"--batch {args.batch} must be divisible by --accum-steps "
            f"{args.accum_steps}"
        )
    telemetry = (EventLog.to_dir(args.telemetry_dir) if args.telemetry_dir
                 else EventLog())
    tc = TrainConfig(
        optimizer=args.optimizer, learning_rate=lr,
        weight_decay=args.weight_decay, total_steps=args.steps, seed=args.seed,
        accum_steps=args.accum_steps, precision=args.precision,
        use_fused_lamb=args.fused_lamb,
        skip_nonfinite=args.skip_nonfinite,
        log_trust_ratios=args.log_trust_ratios,
        # per-layer recording costs a host transfer per logged step — only
        # worth it when there is an event log to receive it
        record_trust_ratios=args.log_trust_ratios and telemetry.enabled,
    )
    trainer = Trainer(
        model, tc,
        schedule=core.warmup_poly_decay(
            lr, args.steps, int(args.steps * warmup_ratio)),
        mesh=mesh,
        checkpoint_dir=args.checkpoint_dir or None,
        checkpoint_every=args.checkpoint_every,
        async_checkpoint=args.async_checkpoint,
        resume=args.resume,
        log_every=args.log_every,
        telemetry=telemetry,
        supervisor=(
            SupervisorConfig(spike_window=args.spike_window,
                             max_rollbacks=args.max_rollbacks)
            if args.rollback_on_spike else None
        ),
        preempt_grace=args.preempt_grace,
    )

    if args.mixed_batch:
        stages = [
            make_stage("stage1", args.seq, args.batch,
                       int(args.steps * 0.8), base_lr=args.base_lr,
                       base_batch=args.base_batch,
                       base_warmup_ratio=args.warmup_ratio),
            make_stage("stage2_rewarmup", args.seq * 4, max(args.batch // 4, 1),
                       args.steps - int(args.steps * 0.8),
                       base_lr=args.base_lr, base_batch=args.base_batch,
                       base_warmup_ratio=args.warmup_ratio),
        ]
        # every stage batch must slice into accum_steps microbatches AND
        # split over the mesh's data axes, else stage 2 would crash after
        # stage 1 already trained
        from repro.sharding import dp_size

        dp = 1 if mesh is None else dp_size(mesh)
        for st in stages:
            if st.batch_size % args.accum_steps:
                raise SystemExit(
                    f"stage {st.name!r} batch {st.batch_size} is not "
                    f"divisible by --accum-steps {args.accum_steps}"
                )
            if st.batch_size % dp:
                raise SystemExit(
                    f"stage {st.name!r} batch {st.batch_size} is not "
                    f"divisible by the mesh's data-parallel size {dp}"
                )
    # the Trainer emits run_end (with status) from a finally, so the report
    # is written even when the run aborts — a diverged run's RUN_REPORT is
    # exactly the diagnostic artifact you want to inspect
    exit_code = 0
    try:
        if args.mixed_batch:
            trainer.fit_stages(stages, data_seed=args.seed)
        else:
            def make_data():
                return DataPipeline(cfg, args.batch, args.seq,
                                    seed=args.seed, mesh=mesh)

            trainer.fit(make_data(), args.steps, data_factory=make_data)
    except DivergenceError as e:
        print(f"DIVERGED: {e}", file=sys.stderr)
        for k, v in e.diagnostics.items():
            print(f"  {k}: {v}", file=sys.stderr)
        exit_code = 3
    finally:
        if telemetry.enabled:
            report_path = Path(args.telemetry_dir) / "RUN_REPORT.json"
            RunReport.from_events(telemetry.path).write(report_path)
            print(f"telemetry: {telemetry.path} report: {report_path}")

    final = trainer.history[-1] if trainer.history else {}
    loss = final.get("loss/total")
    print(f"done: step={final.get('step')} "
          f"loss={'n/a' if loss is None else f'{loss:.4f}'} "
          f"acc={final.get('accuracy', 0.0):.4f} status={trainer._status}")
    if exit_code:
        sys.exit(exit_code)


if __name__ == "__main__":
    main()
