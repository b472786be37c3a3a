"""Training cells: a configuration under LAMB, through the jitted step that
``Trainer`` builds, fed by ``DataPipeline`` prefetching the traffic
generator's batches for the mix's objective (masked-LM or next-token).

Set-up builds the trainer and its state (weights drawn on the device from
the seed in one jitted call), then drives that same step through its
first steps on the window's own feed; their losses, the first gradient as
LAMB's first moment holds it, and the weights' change after three steps
are kept for the check.  A few more steps warm up, then the window runs
for ``--seconds``.  Once it has closed and the program's state is freed,
the plain reference (``configs/<config>.ref.py``, under the shared
``chipbench/ref_lamb.py``) follows the same first steps in float32 and the
readings are compared.
"""
from __future__ import annotations

import gc
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import BENCH_DIR
from chipbench import common as cm
from chipbench import ref_lamb
from chipbench import trace as tr
from chipbench.traffic import first_batches, predictions_per_row, train_batches

CHECK_STEPS = 3


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves (layer slices) of |prog - ref| / max(ref, median
    ref), ``keep`` naming the slices compared (default: all)."""
    allref = np.concatenate([np.ravel(v) for v in ref.values()])
    med = float(np.median(allref))
    worst = 0.0
    for k, r in ref.items():
        p = np.ravel(prog[k])
        r = np.ravel(r)
        mask = np.ones_like(r, bool) if keep is None else np.ravel(keep[k])
        if mask.any():
            gap = np.abs(p - r)[mask] / np.maximum(r[mask], med)
            worst = max(worst, float(gap.max()))
    return worst


def moving_slices(ref_grads: dict) -> dict:
    """Slices whose reference gradient is more than a thousandth of the
    median slice's; others move under LAMB by round-off alone."""
    med = float(np.median(np.concatenate([np.ravel(v)
                                          for v in ref_grads.values()])))
    return {k: np.ravel(v) > 1e-3 * med for k, v in ref_grads.items()}


def readings(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss_gap = math.inf
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": worst_leaf_gap(prog["grad_norms"],
                                        ref["grad_norms"]),
        "change_norm_gap": worst_leaf_gap(prog["change_norms"],
                                          ref["change_norms"],
                                          moving_slices(ref["grad_norms"])),
    }


def in_sharding(step: Callable, ctx) -> Callable:
    """The step traced and run under the trainer's sharding context, as
    ``Trainer.fit`` runs it: without the context the model's activation
    sharding rules do not hold, and on a mesh XLA keeps the whole batch's
    activations on every chip (bert-large at batch 128 on four chips then
    asks 25 GB a chip)."""
    from repro.sharding.context import use_sharding

    def run(state, batch):
        with use_sharding(ctx):
            return step(state, batch)

    return run


class Program:
    """The system under test, built once: model, trainer, compiled step."""

    def __init__(self, cell, step_hook: Optional[Callable] = None):
        from repro import core
        from repro.configs.base import TrainConfig
        from repro.launch.mesh import make_mesh_from_spec
        from repro.models import build_model
        from repro.train import Trainer

        t = cell.traffic
        self.cell, self.job = cell, t["optimizer"]
        self.mc = cm.program_config(cell.config, t)
        self.model = build_model(self.mc)
        j = self.job
        tc = TrainConfig(
            optimizer="lamb", learning_rate=j["learning_rate"],
            total_steps=j["total_steps"], weight_decay=j["weight_decay"],
            b1=j["b1"], b2=j["b2"], eps=j["eps"],
            grad_clip_norm=j["grad_clip_norm"], precision=t["precision"],
            use_fused_lamb=t["fused_lamb"], seed=0)
        schedule = core.warmup_poly_decay(j["learning_rate"], j["total_steps"],
                                          j["warmup_steps"])
        self.mesh = make_mesh_from_spec(t["mesh"]) if t.get("mesh") else None
        self.trainer = Trainer(self.model, tc, schedule=schedule,
                               mesh=self.mesh, log_every=1 << 30,
                               log_fn=cm.log)
        # the program's private seams (PERF.md, Open questions): the jitted
        # step, the state's shapes and placement, and the batch placement
        self.step = cm.seam(self.trainer, "_step_fn")
        if self.mesh is not None:
            self.step = in_sharding(self.step, self.trainer.shard_ctx)
        if step_hook is not None:
            self.step = step_hook(self.step)
        self.place = cm.seam(self.trainer, "_place_batch")
        ref, cfg = cell.reference, cell.config
        self.abstract = abstract = cm.seam(self.trainer, "_abstract_state")
        cm.check_layout(jax.eval_shape(lambda k: ref.init_params(cfg, k),
                                       jax.random.key(0)), abstract.params)

        def init_state(kd):
            params = ref.init_params(cfg, jax.random.wrap_key_data(kd))
            params = jax.tree.map(lambda a, s: a.astype(s.dtype), params,
                                  abstract.params)
            zeros = lambda s: jnp.zeros(s.shape, s.dtype)  # noqa: E731
            return abstract._replace(
                params=params, opt_state=jax.tree.map(zeros, abstract.opt_state),
                step=zeros(abstract.step), skipped=zeros(abstract.skipped))

        def slice_norms(tree):
            return ref_lamb.slice_norms(tree, ref.slice_axes)

        def change(params, kd):
            w0 = ref.init_params(cfg, jax.random.wrap_key_data(kd))
            return slice_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b, params, w0))

        sharding = cm.seam(self.trainer, "_state_sharding")
        self.init_state = jax.jit(
            init_state, **({} if sharding is None
                           else {"out_shardings": sharding}))
        self.change_norms = jax.jit(change)
        self.moment_norms = jax.jit(slice_norms)

    def pipeline(self, seed: int):
        from repro.data import DataPipeline

        t = self.cell.traffic
        pipe = DataPipeline(self.mc, t["global_batch"], t["seq_len"],
                            mesh=self.mesh, prefetch=t["prefetch"])
        cm.seam(pipe, "_it")
        pipe._it = train_batches(t, self.cell.config, seed)
        return pipe

    def first_steps(self, seed: int):
        """Fresh state from ``seed``, driven through the check's steps on
        the window's own call and feed.  Returns (state, pipe, readings)."""
        kd = cm.key_data(seed)
        with jax.threefry_partitionable(True):
            state = self.init_state(kd)
        pipe = self.pipeline(seed)
        first = first_batches(self.cell.traffic, self.cell.config, seed, 1)[0]
        losses, grads = [], None
        for i in range(CHECK_STEPS):
            batch = next(pipe)
            if i == 0 and not np.array_equal(np.asarray(batch["tokens"]),
                                             first["tokens"]):
                raise RuntimeError("DataPipeline does not feed the "
                                   "benchmark's batches")
            state, metrics = self.step(state, self.place(batch))
            losses.append(metrics["loss/total"])
            if i == 0:
                mu = self.moment_norms(state.opt_state.mu)
                grads = {k: np.asarray(v) / (1.0 - self.job["b1"])
                         for k, v in jax.device_get(mu).items()}
        with jax.threefry_partitionable(True):
            change = jax.device_get(self.change_norms(state.params, kd))
        prog = {"losses": [float(x) for x in jax.device_get(losses)],
                "grad_norms": grads,
                "change_norms": {k: np.asarray(v) for k, v in change.items()}}
        return state, pipe, prog


def reference_readings(cell, seed: int, mm=None, rows_from: int = 0) -> dict:
    """The plain reference over the same first batches, in float32."""
    t, ref = cell.traffic, cell.reference
    batches = [(b["tokens"][rows_from:], b["labels"][rows_from:])
               for b in first_batches(t, cell.config, seed, CHECK_STEPS)]
    kd = cm.key_data(seed)
    with jax.threefry_partitionable(True):
        return ref_lamb.lamb_steps(ref, cell.config, t["optimizer"],
                                   jax.random.wrap_key_data(kd), batches,
                                   t["reference_rows"],
                                   ref.highest if mm is None else mm)


def counters_for(cell, program: Program, tokens_per_s: float,
                 steps_traced: int) -> dict:
    t, c = cell.traffic, cell.config
    chips = cell.chips
    return {
        "tokens_per_s": tokens_per_s, "chips": chips,
        "seq_len": t["seq_len"], "batch_per_chip": t["global_batch"] // chips,
        "n_pred": predictions_per_row(t), "steps_traced": steps_traced,
        "n_params": sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
            program.abstract.params)),
        "heads": c["num_attention_heads"],
        "head_dim": c.get("head_dim",
                          c["hidden_size"] // c["num_attention_heads"]),
    }


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, step_hook: Optional[Callable] = None) -> dict:
    t = cell.traffic
    program = Program(cell, step_hook)
    state, pipe, prog = program.first_steps(seed)
    place = program.place
    for _ in range(t["warmup_steps_extra"]):
        state, metrics = program.step(state, place(next(pipe)))
    jax.block_until_ready(state)
    compiles = cm.CompileCounter()
    collector = cm.GcWatch()
    watch = cm.HostWatch()
    tokens_per_step = t["global_batch"] * t["seq_len"]
    traced = cm.Traced(BENCH_DIR / ".traces" / cell.name) if trace else None

    t_w0 = cm.now()
    setup_s = t_w0 - t_start
    losses, n, steps_traced, t_rate0, n_rate0 = [], 0, 0, t_w0, 0
    done = []  # host time each step was seen finished
    parts = []  # (input, dispatch, device wait) of the turn ending there
    if traced:
        traced.start()
    while True:
        t_a = cm.now()
        with jax.profiler.TraceAnnotation("bench:input"):
            batch = place(next(pipe))
        t_b = cm.now()
        with jax.profiler.StepTraceAnnotation("bench:train_step", step_num=n):
            state, metrics = program.step(state, batch)
        t_c = cm.now()
        losses.append(metrics["loss/total"])
        n += 1
        if n >= 2:
            losses[n - 2].block_until_ready()
            done.append(cm.now())
            parts.append((t_b - t_a, t_c - t_b, done[-1] - t_c))
        if traced and traced.trace is None and n == t["trace_steps"]:
            jax.block_until_ready(state)
            traced.stop()
            steps_traced, t_rate0, n_rate0 = n, cm.now(), n
        # a traced run reads its rate from the steps after the trace
        if cm.now() - t_w0 >= seconds and (
                not traced or n - n_rate0 >= t["trace_steps"]):
            break
    jax.block_until_ready(state)
    t_w1 = cm.now()
    watch.close()
    collections = collector.close()
    window = t_w1 - t_w0
    rate = (n - n_rate0) * tokens_per_step / (t_w1 - t_rate0)
    host_losses = [float(x) for x in jax.device_get(losses)]
    failed = sum(not math.isfinite(x) for x in host_losses)
    device = cm.device_info(devices[:cell.chips])
    after = [(x, p) for x, p in zip(done, parts) if x >= t_rate0]
    detail = [f"input {a:.3f} s, dispatch {b:.3f} s, device wait {c:.3f} s"
              for _, (a, b, c) in after]
    cm.log(f"window: {n} steps in {window:.3f} s, {failed} non-finite; "
           f"set-up {setup_s:.3f} s; compilations in the window "
           f"{compiles.n}; peak HBM {device['memory_peak_bytes']}; "
           f"{cm.longest_pause([x for x, _ in after], t_rate0, watch, detail)}"
           f"; "
           f"{collections}")

    metrics_out, breakdown = {}, None
    if traced:
        trace_ = traced.trace
        device["busy_s"], device["window_s"] = tr.busy_s(trace_), \
            tr.window_s(trace_)
        ctx = cm.Ctx(cell, trace_, counters_for(cell, program, rate,
                                                steps_traced),
                     devices[0].device_kind)
        metrics_out = cm.read_per_layer(cell, ctx)
        breakdown = {"device_ops": tr.top_ops(trace_),
                     "idle_gaps": tr.idle_gaps(trace_)}
        traced.cleanup()
    else:
        e2e = {"train_tokens_per_s": n * tokens_per_step / window,
               "setup_s": setup_s}
        for m in cell.metrics("end_to_end"):
            metrics_out[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # free the program's state before the reference runs
    program.trainer.state = None
    del state, pipe, metrics, batch, losses, program
    gc.collect()
    ref = reference_readings(cell, seed)
    ok, checks = cm.judge(readings(prog, ref), cell.limits)
    return dict(correct=ok and failed == 0, attempted=n, failed=failed,
                metrics=metrics_out, device=device, checks=checks,
                breakdown=breakdown)
