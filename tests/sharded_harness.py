"""Multi-device harness for the sharded train path (run as a subprocess).

Forces 8 virtual CPU devices via XLA_FLAGS *before* importing jax — the flag
only takes effect at backend init, which is why tests/test_sharded_train.py
runs this file as a subprocess (the pytest process already initialized jax
on the single real CPU device; same pattern as the production dry-run).

    PYTHONPATH=src python tests/sharded_harness.py [scenario ...]

Prints one JSON object on the last stdout line.  Scenarios:

  equiv         sharded step ≡ single-device step (unfused / fused /
                accum2+bf16, on data=8 and data=4,model=2 meshes)
  lans          LANS sharded ≡ single-device (fp32 and accum2+bf16): the
                per-slice gradient-norm reductions under GSPMD
  mlm_flash     the paper path: bert-smoke MLM through flash attention,
                fused LAMB and the fused-CE head (plus the dense-head
                variant), sharded ≡ single-device
  mlm_kernels   the same path with flash, the fused CE head and fused LAMB
                on their Pallas kernels (interpreted): on a mesh they run
                under shard_map, sharded ≡ single-device
  stages        mixed-batch fit_stages re-jits correctly on a mesh
  checkpoint    FSDP state saved on data=8 restores onto data=4,model=2
                (values, placements, and a post-restore step)
  crash_resume  preemption/fault injection: nested training subprocesses
                are SIGKILLed mid-training and mid-save (a hook inside the
                checkpoint write path), then resumed — on the same data=8
                mesh (bit-exact loss/metric continuation vs an
                uninterrupted reference) and on data=4,model=2 — with
                crash-consistency checks on the checkpoint directory
                (LATEST never names a partial checkpoint; stray tmp dirs
                are GC'd by the resumed run's first save)
  memory        per-device param+optimizer bytes: FSDP vs unsharded, live
                arrays + compiled per-device argument sizes
  guards        clear errors for non-divisible batches
  nan_skip      in-jit non-finite guard under GSPMD: a NaN-injected batch is
                skipped in-graph (global reduction — every device agrees)
                and the final params are BITWISE equal to a clean run whose
                stream simply omits the poisoned ordinal; both meshes
  spike_rollback  loss-spike watchdog on a mesh: an injected spike trips the
                supervisor, the last validated checkpoint is restored, the
                stream fast-forwards past the suspect window, and the run
                completes with finite loss; both meshes
  sigterm_resume  SIGTERM preemption: a victim gets SIGTERM mid-run, writes
                a final checkpoint inside the grace window, exits rc=0 with
                status=preempted, and a --resume run continues bit-exact vs
                an uninterrupted reference (data=8)

The ``--victim`` mode is the nested training run the crash_resume /
sigterm_resume scenarios kill (or signal) and resume:

    python tests/sharded_harness.py --victim --ckpt-dir D --steps 8 \
        --every 2 --mesh data=8,model=1 [--resume] [--out hist.json] \
        [--kill-after-batches 5 | --kill-at-save 2:3] [--sync-checkpoint] \
        [--term-after-batches 5 --preempt-grace 30] [--skip-nonfinite]
"""
import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import (  # noqa: E402
    checkpoint_step,
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from repro.configs import smoke_config  # noqa: E402
from repro.configs.base import ModelConfig, TrainConfig  # noqa: E402
from repro.core import make_stage  # noqa: E402
from repro.data import DataPipeline  # noqa: E402
from repro.launch.mesh import make_mesh_from_spec  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.sharding import (  # noqa: E402
    shardings_for,
    train_state_shardings,
    use_sharding,
)
from repro.telemetry import EventLog  # noqa: E402
from repro.train import (  # noqa: E402
    FaultInjector,
    FaultSpec,
    SupervisorConfig,
    Trainer,
)
from repro.train.step import make_train_step  # noqa: E402

TINY = ModelConfig(
    name="tiny-sharded", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
)
MESHES = ("data=8,model=1", "data=4,model=2")
BATCH, SEQ, STEPS = 16, 32, 3


def _fit(cfg, tc, mesh_spec=None, steps=STEPS, batch=BATCH, seq=SEQ):
    mesh = make_mesh_from_spec(mesh_spec) if mesh_spec else None
    model = build_model(cfg)
    tr = Trainer(model, tc, mesh=mesh, log_every=1000, log_fn=lambda s: None)
    data = DataPipeline(cfg, batch, seq, seed=0, mesh=mesh)
    tr.fit(data, steps)
    return tr


def _maxdiff(a, b) -> float:
    # gather to host first: operands may be committed to different meshes
    d = jax.tree.map(
        lambda x, y: float(
            np.max(np.abs(
                np.asarray(x).astype(np.float32)
                - np.asarray(y).astype(np.float32)
            ))
        ),
        a, b,
    )
    return max(jax.tree.leaves(d))


def _equiv_entry(cfg, tc):
    base = _fit(cfg, tc)
    out = {}
    for spec in MESHES:
        tr = _fit(cfg, tc, spec)
        out[spec] = {
            "param_maxdiff": _maxdiff(tr.state.params, base.state.params),
            "loss_diff": abs(
                tr.history[-1]["loss/total"] - base.history[-1]["loss/total"]
            ),
            "loss": tr.history[-1]["loss/total"],
        }
    return out


def scenario_equiv():
    return {
        "unfused": _equiv_entry(
            TINY, TrainConfig(optimizer="lamb", learning_rate=1e-3)
        ),
        "fused": _equiv_entry(
            TINY,
            TrainConfig(optimizer="lamb", learning_rate=1e-3,
                        use_fused_lamb=True),
        ),
        "accum2_bf16": _equiv_entry(
            TINY,
            TrainConfig(optimizer="lamb", learning_rate=1e-3, accum_steps=2,
                        precision="bf16"),
        ),
    }


def scenario_lans():
    """LANS (block-normalized gradient, Nesterov two-term trust-ratio update)
    sharded ≡ single-device — plain fp32 and the accum+bf16 large-batch
    config, on both mesh shapes.  LANS rides the unfused transform chain, so
    this pins the per-slice gradient-norm reductions under GSPMD."""
    return {
        "fp32": _equiv_entry(
            TINY, TrainConfig(optimizer="lans", learning_rate=1e-3)
        ),
        "accum2_bf16": _equiv_entry(
            TINY,
            TrainConfig(optimizer="lans", learning_rate=1e-3, accum_steps=2,
                        precision="bf16"),
        ),
    }


def scenario_mlm_flash():
    # MLM through flash attention; the smoke config inherits bert-large's
    # use_flash_kernel=True AND use_fused_ce_head=True, so "fused_ce" is the
    # full paper path (gather + chunked-vocab CE head, no (B,S,V) logits)
    # and "dense_head" isolates the head swap on the same sharded step
    cfg = smoke_config("bert-large")
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True)
    return {
        "fused_ce": _equiv_entry(cfg, tc),
        "dense_head": _equiv_entry(cfg.replace(use_fused_ce_head=False), tc),
    }


def scenario_mlm_kernels():
    """The paper path with every kernel family on its Pallas path (run by
    the interpreter): on a mesh the kernel calls go through shard_map, so
    GSPMD never has to partition them.  Sharded must still match
    single-device, and the sharded step must hold the manual regions."""
    from repro.kernels import ops

    cfg = smoke_config("bert-large").replace(fused_ce_backend="interpret")
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True,
                     fused_backend="interpret")
    resolve = ops.resolve_flash_backend
    ops.resolve_flash_backend = lambda backend="auto": "interpret"
    try:
        out = _equiv_entry(cfg, tc)
        tr = _fit(cfg, tc, MESHES[0], steps=1)
        batch = next(DataPipeline(cfg, BATCH, SEQ, seed=0, mesh=tr.mesh))
        with use_sharding(tr.shard_ctx):
            text = tr._step_fn.lower(tr.state, batch).as_text()
        out["manual_regions"] = text.count("sdy.manual_computation")
    finally:
        ops.resolve_flash_backend = resolve
    return out


def scenario_stages():
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True)
    mesh = make_mesh_from_spec("data=8,model=1")
    model = build_model(TINY)
    tr = Trainer(model, tc, mesh=mesh, log_every=1000, log_fn=lambda s: None)
    stages = [
        make_stage("s1", SEQ, 16, 2, base_lr=1e-3, base_batch=16,
                   base_warmup_ratio=0.25),
        make_stage("s2", SEQ * 2, 8, 2, base_lr=1e-3, base_batch=16,
                   base_warmup_ratio=0.25),
    ]
    tr.fit_stages(stages)
    return {
        "final_step": int(tr.state.step),
        "final_loss": tr.history[-1]["loss/total"],
        "finite": bool(np.isfinite(tr.history[-1]["loss/total"])),
    }


def scenario_checkpoint(tmpdir="/tmp/sharded_harness_ckpt"):
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True)
    tr = _fit(TINY, tc, "data=8,model=1", steps=2)
    path = save_checkpoint(tmpdir, int(tr.state.step), tr.state)

    # restore the full TrainState onto a *different* mesh shape
    mesh2 = make_mesh_from_spec("data=4,model=2")
    model = build_model(TINY)
    init_fn, step_fn = make_train_step(model, tc)
    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    ssh2 = train_state_shardings(model.defs, abstract, mesh2)
    restored = restore_checkpoint(path, abstract, shardings=ssh2)

    param_maxdiff = _maxdiff(restored.params, tr.state.params)
    moment_maxdiff = _maxdiff(restored.opt_state.mu, tr.state.opt_state.mu)
    # every restored leaf must be committed to its target sharding
    flat_ok = all(
        leaf.sharding == sh
        for leaf, sh in zip(
            jax.tree.leaves(restored.params), jax.tree.leaves(ssh2.params)
        )
    )
    # the restored state must be usable: one more sharded step on mesh2
    tr2 = Trainer(model, tc, mesh=mesh2, log_every=1000, log_fn=lambda s: None)
    tr2.state = restored
    data = DataPipeline(TINY, BATCH, SEQ, seed=1, mesh=mesh2)
    tr2.fit(data, 1)
    return {
        "param_maxdiff": param_maxdiff,
        "moment_maxdiff": moment_maxdiff,
        "shardings_match": bool(flat_ok),
        "post_restore_step": int(tr2.state.step),
        "post_restore_loss_finite": bool(
            np.isfinite(tr2.history[-1]["loss/total"])
        ),
    }


# ---------------------------------------------------------------------------
# preemption / fault injection: SIGKILL a nested training run, resume it
# ---------------------------------------------------------------------------

def _kill_after_batches(data, n: int):
    """Yield ``n`` batches, then SIGKILL the process on the next request —
    a preemption landing at a chosen training step."""
    served = 0
    while True:
        if served >= n:
            os.kill(os.getpid(), signal.SIGKILL)
        served += 1
        yield next(data)


def _term_after_batches(data, n: int):
    """Send the process SIGTERM once, when the ``n``-th batch is requested,
    then keep serving — the *graceful* preemption: the handler sets a flag,
    the in-flight step finishes, the Trainer saves and stops cleanly."""
    served = 0
    while True:
        if served == n:
            os.kill(os.getpid(), signal.SIGTERM)
        served += 1
        yield next(data)


def _arm_mid_save_kill(save_idx: int, leaf_idx: int) -> None:
    """SIGKILL during the ``save_idx``-th checkpoint write of this process,
    once ``leaf_idx`` leaves are on disk — i.e. mid-save, before the atomic
    rename publishes the checkpoint."""
    from repro.checkpoint import io as ckpt_io

    seen = {"saves": 0}

    def hook(i, _tmp):
        if i == 0:
            seen["saves"] += 1
        if seen["saves"] == save_idx and i == leaf_idx:
            os.kill(os.getpid(), signal.SIGKILL)

    ckpt_io.after_leaf_write = hook


def victim(argv) -> None:
    """One nested training run the crash_resume scenario kills / resumes."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--every", type=int, default=2)
    ap.add_argument("--mesh", default=MESHES[0])
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--sync-checkpoint", action="store_true")
    ap.add_argument("--kill-after-batches", type=int, default=None)
    ap.add_argument("--kill-at-save", default=None, metavar="SAVE:LEAF")
    ap.add_argument("--term-after-batches", type=int, default=None)
    ap.add_argument("--preempt-grace", type=float, default=None)
    ap.add_argument("--skip-nonfinite", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    if args.kill_at_save:
        save_idx, leaf_idx = (int(x) for x in args.kill_at_save.split(":"))
        _arm_mid_save_kill(save_idx, leaf_idx)

    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True,
                     skip_nonfinite=args.skip_nonfinite)
    mesh = make_mesh_from_spec(args.mesh)
    tr = Trainer(
        build_model(TINY), tc, mesh=mesh,
        checkpoint_dir=args.ckpt_dir or None, checkpoint_every=args.every,
        async_checkpoint=not args.sync_checkpoint, resume=args.resume,
        preempt_grace=args.preempt_grace,
        log_every=1, log_fn=lambda s: None,
    )
    data = DataPipeline(TINY, BATCH, SEQ, seed=0, mesh=mesh)
    if args.kill_after_batches is not None:
        data = _kill_after_batches(data, args.kill_after_batches)
    if args.term_after_batches is not None:
        data = _term_after_batches(data, args.term_after_batches)
    tr.fit(data, args.steps)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": tr.history,
                       "final_step": int(tr.state.step),
                       "skipped": int(tr.state.skipped),
                       "status": tr._status,
                       "examples_seen": tr.examples_seen}, f)


def _run_victim(*args, expect_kill=False, timeout=600):
    cmd = [sys.executable, os.path.abspath(__file__), "--victim",
           *map(str, args)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
    if expect_kill:
        if proc.returncode != -signal.SIGKILL:
            raise RuntimeError(
                f"victim survived (rc={proc.returncode}):\n"
                f"{proc.stderr[-3000:]}"
            )
    elif proc.returncode != 0:
        raise RuntimeError(
            f"victim failed (rc={proc.returncode}):\n{proc.stderr[-3000:]}"
        )
    return proc


def _history_rows(blob, after_step):
    """History rows past ``after_step``, minus wall-clock (machine noise)."""
    return [
        {k: v for k, v in row.items() if k != "wall_s"}
        for row in blob["history"] if row["step"] > after_step
    ]


def _stray_tmp_count(ckpt_dir):
    return sum(n.startswith(".tmp_ckpt_") for n in os.listdir(ckpt_dir))


def scenario_crash_resume(steps=8, every=2):
    """Kill-and-resume: the acceptance gate for crash-safe training.

    An uninterrupted reference run (no checkpointing) fixes the ground-truth
    loss/metric history.  Victims are SIGKILLed mid-training and mid-save,
    resumed from the latest *persisted* checkpoint on the same data=8 mesh
    (history must be bit-exact vs the reference from the restored step on)
    and on a data=4,model=2 mesh (allclose — cross-mesh reduction order),
    with crash-consistency checks on the directory in between.
    """
    mesh_a, mesh_b = MESHES
    results = {}
    with tempfile.TemporaryDirectory() as root:
        ref_json = os.path.join(root, "ref.json")
        _run_victim("--steps", steps, "--mesh", mesh_a, "--out", ref_json)
        with open(ref_json) as f:
            ref = json.load(f)

        def crash_then_inspect(name, *kill_args):
            ckpt = os.path.join(root, name)
            _run_victim("--ckpt-dir", ckpt, "--steps", steps,
                        "--every", every, "--mesh", mesh_a, *kill_args,
                        expect_kill=True)
            latest = latest_checkpoint(ckpt)
            with open(os.path.join(ckpt, "LATEST")) as f:
                pointed = os.path.join(ckpt, f.read().strip())
            return ckpt, {
                "latest_step": (None if latest is None
                                else checkpoint_step(latest)),
                "pointer_names_complete": os.path.isfile(
                    os.path.join(pointed, "manifest.json")),
                "stray_tmp_dirs": _stray_tmp_count(ckpt),
            }

        def resume_and_compare(ckpt, entry, mesh):
            res_json = ckpt + f"_resume_{mesh.replace('=', '').replace(',', '_')}.json"
            _run_victim("--ckpt-dir", ckpt, "--steps", steps,
                        "--every", every, "--mesh", mesh, "--resume",
                        "--out", res_json)
            with open(res_json) as f:
                res = json.load(f)
            start = entry["latest_step"]
            rows, ref_rows = _history_rows(res, start), _history_rows(ref, start)
            return {
                "resumed_rows": len(rows),
                "steps_match": ([r["step"] for r in rows]
                                == [r["step"] for r in ref_rows]),
                "bitexact": rows == ref_rows,
                "loss_maxdiff": max(
                    abs(a["loss/total"] - b["loss/total"])
                    for a, b in zip(rows, ref_rows)),
                "final_step": res["final_step"],
                "examples_seen_match": (res["examples_seen"]
                                        == ref["examples_seen"]),
                "tmp_gc_after_resume": _stray_tmp_count(ckpt) == 0,
                "final_latest_step": checkpoint_step(latest_checkpoint(ckpt)),
            }

        # -- preemption mid-training: SIGKILL when step 8's batch is pulled
        ckpt1, e1 = crash_then_inspect(
            "mid_training", "--kill-after-batches", steps - 1)
        ckpt1_copy = ckpt1 + "_meshb"
        shutil.copytree(ckpt1, ckpt1_copy)  # B-mesh resume gets a pristine dir
        e1["resume_same_mesh"] = resume_and_compare(ckpt1, e1, mesh_a)
        e1["resume_other_mesh"] = resume_and_compare(
            ckpt1_copy, {"latest_step": e1["latest_step"]}, mesh_b)
        results["mid_training"] = e1

        # -- crash mid-save: die inside the 2nd checkpoint write (step 2*every
        #    stays partial; LATEST must keep naming the complete step `every`)
        ckpt2, e2 = crash_then_inspect("mid_save", "--kill-at-save", "2:3")
        e2["resume_same_mesh"] = resume_and_compare(ckpt2, e2, mesh_a)
        results["mid_save"] = e2
    return results


# ---------------------------------------------------------------------------
# numerical faults: skip-step guard, loss-spike rollback, SIGTERM preemption
# ---------------------------------------------------------------------------

def _drop_ordinal(data, k: int):
    """Yield ``data``'s batches with the ``k``-th one silently omitted —
    the reference stream a guard-skipped run must match exactly."""
    for i, batch in enumerate(data):
        if i != k:
            yield batch


def scenario_nan_skip(steps=6, poison_at=2):
    """Guard equivalence under GSPMD: a NaN-injected run with the guard on
    must land BITWISE on the params of a clean run whose stream omits the
    poisoned ordinal (the skipped step must be a true no-op, and the
    all-finite verdict must be globally uniform across devices)."""
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True,
                     skip_nonfinite=True)
    out = {}
    for spec in MESHES:
        mesh = make_mesh_from_spec(spec)
        model = build_model(TINY)

        inj = FaultInjector([FaultSpec("grad_nan", at=poison_at)])
        tr = Trainer(model, tc, mesh=mesh, log_every=1000, log_fn=lambda s: None)
        tr.fit(inj.wrap(DataPipeline(TINY, BATCH, SEQ, seed=0, mesh=mesh)),
               steps)

        clean = Trainer(model, tc, mesh=mesh, log_every=1000,
                        log_fn=lambda s: None)
        clean.fit(_drop_ordinal(DataPipeline(TINY, BATCH, SEQ, seed=0,
                                             mesh=mesh), poison_at),
                  steps - 1)

        out[spec] = {
            "skipped": int(tr.state.skipped),
            "final_step": int(tr.state.step),
            "param_maxdiff": _maxdiff(tr.state.params, clean.state.params),
            "moment_maxdiff": _maxdiff(tr.state.opt_state.mu,
                                       clean.state.opt_state.mu),
            "steps_match": int(tr.state.step) == int(clean.state.step),
        }
    return out


def scenario_spike_rollback(steps=10, every=2, spike_at=5):
    """Watchdog end-to-end on a mesh: injected loss spike -> supervisor trip
    -> restore last validated checkpoint -> fast-forward past the suspect
    window -> finish with finite loss.  The rollback event carries the
    restore arithmetic the report aggregates."""
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True)
    out = {}
    for spec in MESHES:
        mesh = make_mesh_from_spec(spec)
        model = build_model(TINY)
        inj = FaultInjector([FaultSpec("loss_spike", at=spike_at, scale=100.0)])
        log = EventLog.memory()
        with tempfile.TemporaryDirectory() as ckpt:
            def make_data():
                return inj.wrap(DataPipeline(TINY, BATCH, SEQ, seed=0,
                                             mesh=mesh))

            tr = Trainer(model, tc, mesh=mesh, checkpoint_dir=ckpt,
                         checkpoint_every=every,
                         supervisor=SupervisorConfig(spike_window=8,
                                                     min_history=3),
                         telemetry=log, log_every=1, log_fn=lambda s: None)
            tr.fit(make_data(), steps, data_factory=make_data)
        rollbacks = [e for e in log.events if e["event"] == "rollback"]
        end = [e for e in log.events if e["event"] == "run_end"][-1]
        dropped = sum(e["batches_dropped"] for e in rollbacks)
        out[spec] = {
            "rollbacks": len(rollbacks),
            "reason": rollbacks[0]["reason"] if rollbacks else None,
            "restored_step": rollbacks[0]["step"] if rollbacks else None,
            "from_step": rollbacks[0]["from_step"] if rollbacks else None,
            "final_step": int(tr.state.step),
            # every batch is either trained or explicitly dropped
            "step_arithmetic_ok": int(tr.state.step) == steps - dropped,
            "final_loss": tr.history[-1]["loss/total"],
            "final_loss_finite": bool(
                np.isfinite(tr.history[-1]["loss/total"])),
            "status": end["status"],
        }
    return out


def scenario_sigterm_resume(steps=8, every=3, term_at=5):
    """Graceful preemption on data=8: SIGTERM mid-run -> grace-window final
    save -> clean exit (rc=0, status=preempted) -> --resume continues
    bit-exact vs an uninterrupted reference."""
    mesh = MESHES[0]
    with tempfile.TemporaryDirectory() as root:
        ref_json = os.path.join(root, "ref.json")
        _run_victim("--steps", steps, "--mesh", mesh, "--out", ref_json)
        with open(ref_json) as f:
            ref = json.load(f)

        ckpt = os.path.join(root, "ckpt")
        pre_json = os.path.join(root, "pre.json")
        _run_victim("--ckpt-dir", ckpt, "--steps", steps, "--every", every,
                    "--mesh", mesh, "--term-after-batches", term_at,
                    "--preempt-grace", 60, "--out", pre_json)
        with open(pre_json) as f:
            pre = json.load(f)
        latest = checkpoint_step(latest_checkpoint(ckpt))

        res_json = os.path.join(root, "res.json")
        _run_victim("--ckpt-dir", ckpt, "--steps", steps, "--every", every,
                    "--mesh", mesh, "--resume", "--out", res_json)
        with open(res_json) as f:
            res = json.load(f)
        rows = _history_rows(res, latest)
        ref_rows = _history_rows(ref, latest)
        return {
            "preempt_status": pre["status"],
            "preempt_final_step": pre["final_step"],
            "stopped_early": pre["final_step"] < steps,
            "saved_at_preempt_step": latest == pre["final_step"],
            "resumed_rows": len(rows),
            "bitexact": rows == ref_rows,
            "final_step": res["final_step"],
            "resume_status": res["status"],
        }


def scenario_memory():
    from repro.sharding import per_device_state_bytes

    cfg = smoke_config("bert-large")
    tc = TrainConfig(optimizer="lamb", learning_rate=1e-3, use_fused_lamb=True)
    sharded = _fit(cfg, tc, "data=8,model=1", steps=1)
    single = _fit(cfg, tc, steps=1)

    fsdp = per_device_state_bytes(sharded.state.params) + per_device_state_bytes(
        sharded.state.opt_state
    )
    base = per_device_state_bytes(single.state.params) + per_device_state_bytes(
        single.state.opt_state
    )

    def compiled_arg_bytes(tr, batch):
        try:
            c = tr._step_fn.lower(tr.state, tr._place_batch(batch)).compile()
            ma = c.memory_analysis()
            return {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
            }
        except Exception as e:  # memory_analysis is backend-dependent
            return {"error": f"{type(e).__name__}: {e}"}

    batch = next(DataPipeline(cfg, BATCH, SEQ, seed=0))
    return {
        "fsdp_per_device_state_bytes": fsdp,
        "single_device_state_bytes": base,
        "state_ratio": base / max(fsdp, 1),
        "compiled_sharded": compiled_arg_bytes(sharded, batch),
        "compiled_single": compiled_arg_bytes(single, batch),
    }


def scenario_guards():
    out = {}
    try:
        DataPipeline(TINY, 6, SEQ, mesh=make_mesh_from_spec("data=4,model=2"))
        out["pipeline_raises"] = False
    except ValueError as e:
        out["pipeline_raises"] = True
        out["pipeline_msg"] = str(e)
    try:
        tc = TrainConfig(optimizer="lamb")
        tr = Trainer(build_model(TINY), tc,
                     mesh=make_mesh_from_spec("data=8,model=1"),
                     log_fn=lambda s: None)
        tr.init()
        tr._place_batch({"tokens": np.zeros((6, SEQ), np.int32)})
        out["trainer_raises"] = False
    except ValueError as e:
        out["trainer_raises"] = True
        out["trainer_msg"] = str(e)
    return out


SCENARIOS = {
    "equiv": scenario_equiv,
    "lans": scenario_lans,
    "mlm_flash": scenario_mlm_flash,
    "mlm_kernels": scenario_mlm_kernels,
    "stages": scenario_stages,
    "checkpoint": scenario_checkpoint,
    "crash_resume": scenario_crash_resume,
    "nan_skip": scenario_nan_skip,
    "spike_rollback": scenario_spike_rollback,
    "sigterm_resume": scenario_sigterm_resume,
    "memory": scenario_memory,
    "guards": scenario_guards,
}


def main(argv):
    if argv and argv[0] == "--victim":
        victim(argv[1:])
        return
    names = argv or list(SCENARIOS)
    out = {"devices": len(jax.devices())}
    for name in names:
        out[name] = SCENARIOS[name]()
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
