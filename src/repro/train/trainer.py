"""Trainer: jit'd step loop with metrics, checkpointing and mixed-batch
stages (the paper's two-phase BERT recipe with stage-2 re-warm-up).

Across a stage switch the optimizer *moments* (m, v — ScaleByAdamState /
TraceState) carry over, while schedule counters restart at zero so stage 2
re-warms up — exactly the §4.1 procedure.

Sharded training (``mesh=``): the paper's headline run scales LAMB's batch
across a TPU pod, so the step must actually *run* data-parallel.  Given a
mesh, the Trainer computes explicit placements once at construction —
params and every LAMB moment FSDP-sharded via ``sharding.specs_for`` /
``train_state_shardings``, batches split over the data axes — and jits the
step with ``in_shardings``/``out_shardings`` (+ donated state), so XLA
compiles a true SPMD program instead of inferring layouts from one input.
Parameter init runs under partitionable threefry, making initial values
invariant to the mesh shape (the legacy RNG lowering changes bits when its
output is sharded).

Crash safety (``checkpoint_dir`` + ``checkpoint_every``): every save
persists the *full* ``TrainState`` — params, optimizer moments and the step
counter — so a resume continues optimization instead of silently restarting
it.  ``async_checkpoint=True`` routes saves through the double-buffered
:class:`~repro.checkpoint.async_io.AsyncCheckpointer` (the step loop pays
only the device→host snapshot; the disk write overlaps training), and
``resume=True`` restores the latest complete checkpoint at ``fit`` start,
fast-forwarding the data pipeline so the continuation is bit-exact against
a run that was never interrupted (see docs/reliability.md).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (
    AsyncCheckpointer,
    checkpoint_step,
    discard_checkpoints_after,
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.mixed_batch import Stage
from repro.data.pipeline import DataPipeline
from repro.kernels import FusedLambState
from repro.models.api import Model
from repro.optim.base import ScheduleState
from repro.sharding.axes import batch_axes, dp_size, specs_for
from repro.sharding.context import ShardCtx, use_sharding
from repro.sharding.placement import batch_sharding, train_state_shardings
from repro.telemetry import (
    EventLog,
    SpanRecorder,
    TrustRecorder,
    compile_count,
    run_provenance,
    trace_span,
)
from repro.telemetry.trust import PER_LAYER_KEY
from repro.train.preempt import PreemptionHandler
from repro.train.step import (
    LOSS_KEY,
    TrainState,
    make_optimizer,
    make_train_step,
)
from repro.train.supervisor import (
    DivergenceError,
    SupervisorConfig,
    TrainingSupervisor,
)


def _batch_examples(batch) -> int:
    """Effective global-batch examples in one step: the leading dim of the
    batch handed to step_fn (= microbatch × accum_steps, since accumulation
    slices this same batch internally)."""
    return int(jax.tree.leaves(batch)[0].shape[0])


def _reset_schedule_counts(opt_state):
    """Zero every schedule counter (stage-2 re-warm-up) keeping moments.

    Resets ``ScheduleState.count`` in unfused chains and
    ``FusedLambState.sched_count`` on the fused path; the moment/bias
    counters carry across stages in both cases (§4.1 procedure).
    """

    def is_node(n):
        return isinstance(n, (ScheduleState, FusedLambState))

    def reset(node):
        if isinstance(node, ScheduleState):
            return ScheduleState(count=jnp.zeros_like(node.count))
        if isinstance(node, FusedLambState):
            return node._replace(sched_count=jnp.zeros_like(node.sched_count))
        return node

    return jax.tree.map(reset, opt_state, is_leaf=is_node)


class Trainer:
    def __init__(
        self,
        model: Model,
        train_cfg: TrainConfig,
        *,
        schedule=None,
        mesh=None,
        shard_ctx: Optional[ShardCtx] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        async_checkpoint: bool = False,
        resume: bool = False,
        log_every: int = 10,
        log_fn: Callable[[str], None] = print,
        telemetry: Optional[EventLog] = None,
        supervisor: Optional[SupervisorConfig] = None,
        preempt_grace: Optional[float] = None,
    ):
        self.model = model
        self.tc = train_cfg
        self.mesh = mesh
        self.shard_ctx = shard_ctx
        if mesh is not None and shard_ctx is None:
            self.shard_ctx = ShardCtx(mesh)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        # async: double-buffered background saves (the step loop never
        # blocks on disk); resume: restore the latest persisted full
        # TrainState at fit start and continue from its step
        self.async_checkpoint = async_checkpoint
        self.resume = resume
        self._checkpointer: Optional[AsyncCheckpointer] = None
        # loss-spike watchdog: a fresh TrainingSupervisor is built per fit
        # from this config (rollback counts must not leak across fits)
        self.supervisor_cfg = supervisor
        # preemption: not None installs a SIGTERM/SIGINT handler around the
        # fit loop; the value bounds (seconds) the final-save drain wait
        self.preempt_grace = preempt_grace
        self._last_saved_step: Optional[int] = None
        self._skipped_seen = 0
        self._status = "ok"
        self.log_every = log_every
        self.log = log_fn
        # telemetry: a null EventLog unless the caller wires a real sink;
        # everything below guards on .enabled so the default path does no
        # extra device syncs and history stays bit-identical
        self.telemetry = telemetry if telemetry is not None else EventLog()
        self.spans = SpanRecorder(
            log=self.telemetry if self.telemetry.enabled else None
        )
        self.trust_recorder = TrustRecorder(
            log=self.telemetry if self.telemetry.enabled else None
        )
        self._run_started = False
        self.history: List[Dict[str, float]] = []
        # Effective examples per optimizer step = microbatch × accum_steps:
        # step_fn consumes the already-assembled global batch, so its leading
        # dim *is* the effective global batch regardless of accumulation.
        # Tracking it here keeps history/benchmarks comparable across
        # accumulation settings.
        self.examples_seen: int = 0

        self._param_specs = None
        self._batch_sharding = None
        self._state_sharding = None
        self._dp_size = 1
        if mesh is not None:
            self._param_specs = specs_for(model.defs, mesh)
            self._batch_sharding = batch_sharding(mesh)
            self._dp_size = dp_size(mesh)
        init_fn, step_fn = make_train_step(
            model, train_cfg, schedule, param_specs=self._param_specs
        )
        self._init_fn = init_fn
        # abstract state doubles as the restore target: restore_checkpoint
        # shape/dtype-checks every leaf against it (and, on a mesh, places
        # each leaf straight onto its sharding)
        self._abstract_state = jax.eval_shape(
            init_fn, jax.random.key(train_cfg.seed)
        )
        if mesh is not None:
            self._state_sharding = train_state_shardings(
                model.defs, self._abstract_state, mesh
            )
        self._step_fn = self._jit_step(step_fn)
        self.state: Optional[TrainState] = None

    # ------------------------------------------------------------------
    def _jit_step(self, step_fn: Callable) -> Callable:
        """jit a (state, batch) step, with explicit placements on a mesh.

        Donating the state argument lets XLA update params/moments in place
        — without it the sharded step would double the resident optimizer
        memory.  Metric outputs are left unconstrained (scalars replicate).
        """
        if self._state_sharding is None:
            return jax.jit(step_fn, donate_argnums=(0,))
        return jax.jit(
            step_fn,
            in_shardings=(self._state_sharding, self._batch_sharding),
            out_shardings=(self._state_sharding, None),
            donate_argnums=(0,),
        )

    def _place_batch(self, batch):
        """Device-put a host batch (splitting over the data axes on a mesh)."""
        if self._batch_sharding is None:
            return jax.tree.map(jnp.asarray, batch)
        n = _batch_examples(batch)
        if n % self._dp_size:
            raise ValueError(
                f"global batch {n} is not divisible by the mesh's "
                f"data-parallel size {self._dp_size} "
                f"(axes {batch_axes(self.mesh)}); examples would be dropped"
            )
        def place(x):
            # already committed to the step's layout (DataPipeline(mesh=)):
            # re-placing would gather the global batch to host every step
            if getattr(x, "sharding", None) == self._batch_sharding:
                return x
            return jax.device_put(np.asarray(x), self._batch_sharding)

        return jax.tree.map(place, batch)

    # ------------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> TrainState:
        rng = jax.random.key(self.tc.seed if seed is None else seed)
        # Partitionable threefry makes init values independent of the mesh
        # shape (and of sharded vs single-device execution) — required for
        # the sharded ≡ single-device equivalence this Trainer guarantees.
        with use_sharding(self.shard_ctx), jax.threefry_partitionable(True):
            if self._state_sharding is None:
                self.state = jax.jit(self._init_fn)(rng)
            else:
                self.state = jax.jit(
                    self._init_fn, out_shardings=self._state_sharding
                )(rng)
        return self.state

    # ------------------------------------------------------------------
    def _emit_run_start(self) -> None:
        if self._run_started or not self.telemetry.enabled:
            return
        self._run_started = True
        self.telemetry.emit(
            "run_start",
            provenance=run_provenance(
                mesh=self.mesh, configs=(self.model.cfg, self.tc)
            ),
            arch=self.model.cfg.name,
            optimizer=self.tc.optimizer,
        )

    def _host_metrics(self, metrics):
        """Fetch the whole metrics pytree with ONE ``device_get`` (not one
        blocking sync per metric leaf) and convert on host; pops the
        per-layer telemetry records out of the scalar history."""
        host = jax.device_get(dict(metrics))
        per_layer = host.pop(PER_LAYER_KEY, None)
        return {k: float(v) for k, v in host.items()}, per_layer

    def _log_step(self, m: Dict[str, float], per_layer, step_s: float,
                  n_steps: int) -> None:
        """Emit the log-step telemetry: step event + trust records."""
        scalars = {k: v for k, v in m.items()
                   if k not in ("step", "examples_seen", "wall_s", "stage")}
        ev = dict(step=m["step"], examples_seen=m["examples_seen"],
                  wall_s=m["wall_s"], metrics=scalars,
                  compiles=compile_count())
        if "stage" in m:
            ev["stage"] = m["stage"]
        if n_steps:
            ev["step_time_s"] = step_s / n_steps
        self.telemetry.emit("step", **ev)
        if per_layer is not None:
            self.trust_recorder.record(m["step"], per_layer)

    # ------------------------------------------------------------------
    # checkpointing + resume
    # ------------------------------------------------------------------
    @property
    def checkpointer(self) -> AsyncCheckpointer:
        """Lazy double-buffered async writer (created on first async save)."""
        if self._checkpointer is None:
            self._checkpointer = AsyncCheckpointer(
                self.checkpoint_dir, telemetry=self.telemetry
            )
        return self._checkpointer

    def _save_checkpoint(self) -> None:
        """Persist the FULL TrainState — params, optimizer moments and the
        step counter.  A params-only save silently restarts optimization on
        resume: LAMB's m/v moments and the schedule position are state.

        Same-step re-saves are dropped: with the non-finite guard, skipped
        steps can make two cadence points (or cadence + preemption) land on
        one ``state.step`` — the state is identical, the write is not free.
        """
        step = int(self.state.step)
        if step == self._last_saved_step:
            return
        self._last_saved_step = step
        if self.async_checkpoint:
            self.checkpointer.save(step, self.state)
            return
        t0 = time.perf_counter()
        path = save_checkpoint(self.checkpoint_dir, step, self.state)
        self.telemetry.emit(
            "checkpoint", step=step, path=path, mode="sync",
            write_s=time.perf_counter() - t0,
        )

    def _drain_checkpoints(self, timeout: Optional[float] = None) -> None:
        """Block until the in-flight async write (if any) is durable, so a
        returned ``fit`` implies every scheduled checkpoint is on disk.
        ``timeout`` bounds the wait (the preemption grace window)."""
        if self._checkpointer is not None:
            self._checkpointer.wait(timeout)

    def restore(self, path: Optional[str] = None) -> Optional[int]:
        """Restore the full TrainState from ``path`` (default: the latest
        complete checkpoint in ``checkpoint_dir``).  Returns the restored
        step, or None when there is nothing to restore.  On a mesh each
        leaf is placed straight onto its sharding — a checkpoint written
        on one mesh shape restores onto another."""
        if path is None:
            path = (latest_checkpoint(self.checkpoint_dir)
                    if self.checkpoint_dir else None)
        if path is None:
            return None
        restored = restore_checkpoint(
            path, self._abstract_state, shardings=self._state_sharding
        )
        if self._state_sharding is None:
            restored = jax.tree.map(jnp.asarray, restored)
        self.state = restored
        step = checkpoint_step(path)
        self.telemetry.emit("resume", step=step, path=path)
        self.log(f"resumed step {step} from {path}")
        return step

    def _maybe_resume(self, data, steps: int) -> int:
        """With ``resume=True``, restore the latest checkpoint and return
        the batch ordinal to continue from (0 when none exists).  The
        deterministic data iterator is fast-forwarded past the batches the
        original run already consumed — ``step + skipped``, since a
        guard-skipped step consumed a batch without advancing ``step`` —
        so the resumed run sees exactly the sequence an uninterrupted run
        would: the bit-exact-continuation contract the preemption harness
        asserts."""
        if not self.resume:
            return 0
        step = self.restore()
        if step is None:
            return 0
        self._last_saved_step = step
        start = min(step + int(self.state.skipped), steps)
        for _ in range(start):
            self.examples_seen += _batch_examples(next(data))
        return start

    # ------------------------------------------------------------------
    def fit(self, data, steps: int, *,
            data_factory: Optional[Callable[[], Any]] = None
            ) -> List[Dict[str, float]]:
        """Run the step loop to ``steps`` batches.

        ``data_factory`` (a zero-arg callable rebuilding the deterministic
        iterator ``data`` came from) enables supervisor rollback: on a trip
        the Trainer restores the last validated checkpoint, rebuilds the
        stream, and fast-forwards *past* the suspect batch window.  A
        ``run_end`` event with an explicit status (``ok`` / ``failed`` /
        ``preempted`` / ``diverged``) is emitted from a ``finally`` so
        crashed runs still close their event log.
        """
        if data is None and data_factory is not None:
            data = data_factory()
        start = self._maybe_resume(data, steps)
        if self.state is None:
            self.init()
        self._emit_run_start()
        supervisor = (TrainingSupervisor(self.supervisor_cfg)
                      if self.supervisor_cfg is not None else None)
        self._status = "ok"
        try:
            with PreemptionHandler(
                enabled=self.preempt_grace is not None
            ) as preempt:
                self._fit_loop(data, steps, start, supervisor, preempt,
                               data_factory)
            self._drain_checkpoints()
        except BaseException as e:
            self._status = ("diverged" if isinstance(e, DivergenceError)
                            else "failed")
            raise
        finally:
            self._emit_run_end(supervisor)
        return self.history

    def _fit_loop(self, data, steps: int, start: int,
                  supervisor: Optional[TrainingSupervisor],
                  preempt: PreemptionHandler,
                  data_factory: Optional[Callable[[], Any]]) -> None:
        telem = self.telemetry.enabled
        guard_on = self.tc.skip_nonfinite
        t0 = time.perf_counter()
        since_log = 0
        self._skipped_seen = int(self.state.skipped) if guard_on else 0
        # i is the batch ordinal (stream position), not state.step: a
        # guard-skipped step consumes a batch without advancing step, and
        # the two counters must not be conflated in the loop bookkeeping
        i = start
        with use_sharding(self.shard_ctx):
            while i < steps:
                if telem and since_log == 0:
                    # span boundary: drain prior work so the interval times
                    # only its own steps (async dispatch would otherwise
                    # attribute queued work to the wrong interval)
                    self.spans.start("step", sync=self.state)
                with jax.profiler.StepTraceAnnotation("train.step",
                                                      step_num=i):
                    with trace_span("train.input"):
                        batch = self._place_batch(next(data))
                    self.examples_seen += _batch_examples(batch)
                    with trace_span("train.dispatch"):
                        self.state, metrics = self._step_fn(self.state, batch)
                since_log += 1
                if supervisor is not None:
                    # the watchdog's cost: one blocking host fetch per step
                    loss_d, step_d, skip_d = jax.device_get(
                        (metrics.get(LOSS_KEY), self.state.step,
                         self.state.skipped))
                    loss = float("nan") if loss_d is None else float(loss_d)
                    step_now, skipped_now = int(step_d), int(skip_d)
                    delta = skipped_now - self._skipped_seen
                    self._skipped_seen = skipped_now
                    if delta > 0:
                        self.telemetry.emit(
                            "nonfinite_step", step=step_now, count=delta,
                            total=skipped_now,
                            consecutive=supervisor.consecutive_skips + 1,
                        )
                        self.log(f"non-finite step skipped at batch {i} "
                                 f"(total skipped {skipped_now})")
                    reason = supervisor.observe(step_now, loss, skipped_now)
                    if reason is not None:
                        i, data = self._rollback(
                            reason, supervisor, i, steps, step_now,
                            data_factory,
                        )
                        since_log = 0
                        continue
                if (i + 1) % self.log_every == 0 or i == steps - 1:
                    m, per_layer = self._host_metrics(metrics)
                    step_s = (
                        self.spans.stop("step", sync=self.state,
                                        count=since_log)
                        if telem else 0.0
                    )
                    m["step"] = int(self.state.step)
                    m["examples_seen"] = self.examples_seen
                    m["wall_s"] = time.perf_counter() - t0
                    if guard_on:
                        skipped_now = int(self.state.skipped)
                        m["skipped_total"] = skipped_now
                        if supervisor is None:
                            if skipped_now > self._skipped_seen:
                                self.telemetry.emit(
                                    "nonfinite_step", step=m["step"],
                                    count=skipped_now - self._skipped_seen,
                                    total=skipped_now,
                                )
                            self._skipped_seen = skipped_now
                    self.history.append(m)
                    self.log(
                        f"step {m['step']:6d} loss {m.get('loss/total', 0.0):.4f} "
                        f"acc {m.get('accuracy', 0.0):.4f}"
                    )
                    if telem:
                        self._log_step(m, per_layer, step_s, since_log)
                    since_log = 0
                if (
                    self.checkpoint_dir
                    and self.checkpoint_every
                    and (i + 1) % self.checkpoint_every == 0
                ):
                    self._save_checkpoint()
                i += 1
                if preempt.triggered:
                    self._handle_preempt(preempt)
                    self._status = "preempted"
                    break

    # ------------------------------------------------------------------
    def _rollback(self, reason: str, supervisor: TrainingSupervisor,
                  i: int, steps: int, trip_step: int,
                  data_factory: Optional[Callable[[], Any]]):
        """Restore the last validated checkpoint and fast-forward the data
        stream past the suspect window.  Returns ``(next_i, new_data)``.

        Resuming the stream at ``i + 1`` — not at the restored step — is
        the re-poisoning guard: the batches between the restored checkpoint
        and the trip (the window that contained the poison) are consumed
        untrained, so even a deterministic persistent fault at one ordinal
        can never hit the rolled-back run twice.
        """
        diag = supervisor.diagnostics(reason)
        self.log(f"supervisor trip: {reason} at batch {i} "
                 f"(step {trip_step}, last_good {supervisor.last_good})")
        supervisor.note_rollback(reason)  # raises DivergenceError past budget
        if not self.checkpoint_dir or data_factory is None:
            raise DivergenceError(
                f"diverged ({reason}): rollback needs checkpoint_dir and a "
                "data_factory", diag,
            )
        self._drain_checkpoints()
        bound = supervisor.last_good
        path = (latest_checkpoint(self.checkpoint_dir, max_step=bound)
                if bound >= 0 else None)
        if path is None:
            raise DivergenceError(
                f"diverged ({reason}) before any validated checkpoint "
                f"(last_good step {bound})", diag,
            )
        restored_step = self.restore(path)
        removed = discard_checkpoints_after(self.checkpoint_dir,
                                            restored_step)
        self._last_saved_step = restored_step
        restored_skipped = int(self.state.skipped)
        restored_i = restored_step + restored_skipped
        resume_i = i + 1
        data = data_factory()
        for _ in range(resume_i):
            next(data)  # already consumed pre-trip; examples_seen unchanged
        self.telemetry.emit(
            "rollback", step=restored_step, from_step=trip_step,
            reason=reason, batches_dropped=resume_i - restored_i,
            rollbacks=supervisor.rollbacks, discarded=len(removed),
        )
        supervisor.after_rollback(restored_skipped)
        self._skipped_seen = restored_skipped
        self.log(f"rollback {supervisor.rollbacks}: restored step "
                 f"{restored_step}, dropped batches "
                 f"[{restored_i}, {resume_i}), resuming at batch {resume_i}")
        return resume_i, data

    def _handle_preempt(self, preempt: PreemptionHandler) -> None:
        """Grace-window final save: persist the current full TrainState
        through the existing checkpointer, bounded by ``preempt_grace``."""
        step = int(self.state.step)
        saved = False
        if self.checkpoint_dir:
            self._save_checkpoint()
            if self.async_checkpoint:
                self._drain_checkpoints(timeout=self.preempt_grace)
                saved = (self._checkpointer is not None
                         and self._checkpointer.latest_persisted_step()
                         == step)
            else:
                saved = True
        self.telemetry.emit(
            "preempt", step=step, signal=preempt.signal_name, saved=saved,
            grace_s=float(self.preempt_grace or 0.0),
        )
        self.log(f"preempted ({preempt.signal_name}): step {step} "
                 f"saved={saved}; stopping cleanly")

    def _emit_run_end(self, supervisor: Optional[TrainingSupervisor] = None
                      ) -> None:
        if not self.telemetry.enabled:
            return
        fields: Dict[str, Any] = {"status": self._status}
        try:
            if self.state is not None:
                fields["final_step"] = int(self.state.step)
                fields["skipped_steps"] = int(self.state.skipped)
        except Exception:
            pass  # state may be donated/deleted when aborting mid-step
        if self.history:
            fields["final_loss"] = float(
                self.history[-1].get(LOSS_KEY, float("nan")))
        if supervisor is not None:
            fields["rollbacks"] = supervisor.rollbacks
        self.telemetry.emit("run_end", **fields)

    # ------------------------------------------------------------------
    def fit_stages(
        self, stages: Sequence[Stage], *, data_seed: int = 0
    ) -> List[Dict[str, float]]:
        """Mixed-batch training: re-jit per stage, carry moments, re-warm-up."""
        if self.state is None:
            self.init()
        self._emit_run_start()
        self._status = "ok"
        try:
            self._fit_stages(stages, data_seed=data_seed)
        except BaseException as e:
            self._status = ("diverged" if isinstance(e, DivergenceError)
                            else "failed")
            raise
        finally:
            self._emit_run_end()
        return self.history

    def _fit_stages(self, stages: Sequence[Stage], *, data_seed: int) -> None:
        telem = self.telemetry.enabled
        # one wall clock across all stages, so fit_stages history rows carry
        # the same ``wall_s`` field as fit's and stay comparable
        t0 = time.perf_counter()
        for si, stage in enumerate(stages):
            self.log(
                f"== stage {si}: {stage.name} seq={stage.seq_len} "
                f"batch={stage.batch_size} steps={stage.steps} "
                f"lr={stage.learning_rate:.2e} warmup={stage.warmup_steps}"
            )
            self.telemetry.emit(
                "stage_start", stage=si, name=stage.name,
                seq_len=stage.seq_len, batch_size=stage.batch_size,
                steps=stage.steps, learning_rate=stage.learning_rate,
                warmup_steps=stage.warmup_steps,
            )
            opt = make_optimizer(
                self.model, self.tc, stage.schedule,
                param_specs=self._param_specs,
            )
            _, step_fn = make_train_step(
                self.model, self.tc, stage.schedule, optimizer=opt,
                param_specs=self._param_specs,
            )
            step_jit = self._jit_step(step_fn)
            if si > 0:
                # re-warm-up: keep moments, restart schedule counters
                self.state = TrainState(
                    self.state.params,
                    _reset_schedule_counts(self.state.opt_state),
                    self.state.step,
                    self.state.skipped,
                )
            data = DataPipeline(
                self.model.cfg, stage.batch_size, stage.seq_len, seed=data_seed + si
            )
            since_log = 0
            with use_sharding(self.shard_ctx):
                for i in range(stage.steps):
                    if telem and since_log == 0:
                        self.spans.start("step", sync=self.state)
                    with jax.profiler.StepTraceAnnotation("train.step",
                                                          step_num=i):
                        with trace_span("train.input"):
                            batch = self._place_batch(next(data))
                        self.examples_seen += _batch_examples(batch)
                        with trace_span("train.dispatch"):
                            self.state, metrics = step_jit(self.state, batch)
                    since_log += 1
                    if (i + 1) % self.log_every == 0 or i == stage.steps - 1:
                        m, per_layer = self._host_metrics(metrics)
                        step_s = (
                            self.spans.stop("step", sync=self.state,
                                            count=since_log)
                            if telem else 0.0
                        )
                        m["step"] = int(self.state.step)
                        m["examples_seen"] = self.examples_seen
                        m["wall_s"] = time.perf_counter() - t0
                        m["stage"] = si
                        self.history.append(m)
                        self.log(
                            f"[{stage.name}] step {m['step']:5d} "
                            f"loss {m.get('loss/total', 0.0):.4f}"
                        )
                        if telem:
                            self._log_step(m, per_layer, step_s, since_log)
                        since_log = 0
