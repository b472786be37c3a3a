"""Sharded training end-to-end on 8 virtual CPU devices.

The multi-device work runs in ONE subprocess (tests/sharded_harness.py,
which sets ``--xla_force_host_platform_device_count=8`` before importing
jax — the flag is dead after backend init, so it cannot be set from this
process).  The module-scoped fixture runs every scenario once; the tests
below assert on slices of its JSON report, plus a few in-process unit
checks that need no devices.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# LAMB amplifies reduction-order noise through the trust ratio, so sharded
# vs single-device is allclose, not bitwise (measured ~3e-3 after 3 steps
# on the TP mesh; a placement bug shows up one-plus orders larger).
PARAM_TOL = 2e-2
LOSS_TOL = 1e-2


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)  # the harness sets its own device count
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "sharded_harness.py")],
        capture_output=True, text=True, timeout=1800, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_harness_sees_8_devices(report):
    assert report["devices"] == 8


@pytest.mark.parametrize("variant", ["unfused", "fused", "accum2_bf16"])
@pytest.mark.parametrize("mesh", ["data=8,model=1", "data=4,model=2"])
def test_sharded_step_matches_single_device(report, variant, mesh):
    entry = report["equiv"][variant][mesh]
    assert entry["param_maxdiff"] < PARAM_TOL, entry
    assert entry["loss_diff"] < LOSS_TOL, entry


@pytest.mark.parametrize("variant", ["fp32", "accum2_bf16"])
@pytest.mark.parametrize("mesh", ["data=8,model=1", "data=4,model=2"])
def test_lans_sharded_matches_single_device(report, variant, mesh):
    """LANS normalizes each gradient block by its norm BEFORE the moments, so
    a per-slice reduction that silently went device-local under GSPMD would
    skew every step; sharded must stay allclose to single-device."""
    entry = report["lans"][variant][mesh]
    assert entry["param_maxdiff"] < PARAM_TOL, entry
    assert entry["loss_diff"] < LOSS_TOL, entry


@pytest.mark.parametrize("head", ["fused_ce", "dense_head"])
@pytest.mark.parametrize("mesh", ["data=8,model=1", "data=4,model=2"])
def test_mlm_flash_fused_sharded_matches(report, head, mesh):
    """The paper path: bert MLM through flash attention + fused LAMB, with
    both the fused-CE head (gather + chunked-vocab CE — vocab-chunk
    reductions must stay global under GSPMD) and the dense logits head."""
    entry = report["mlm_flash"][head][mesh]
    assert entry["param_maxdiff"] < PARAM_TOL, entry
    assert entry["loss_diff"] < LOSS_TOL, entry


@pytest.mark.parametrize("mesh", ["data=8,model=1", "data=4,model=2"])
def test_mlm_kernels_under_shard_map_match(report, mesh):
    """The same path on the Pallas kernels (interpreted): GSPMD cannot
    partition a TPU kernel, so on a mesh the kernel calls run per shard
    under shard_map — and the result must not change."""
    entry = report["mlm_kernels"][mesh]
    assert entry["param_maxdiff"] < PARAM_TOL, entry
    assert entry["loss_diff"] < LOSS_TOL, entry
    assert report["mlm_kernels"]["manual_regions"] > 0


def test_mixed_batch_stages_run_sharded(report):
    assert report["stages"]["final_step"] == 4
    assert report["stages"]["finite"]


def test_checkpoint_roundtrips_across_mesh_shapes(report):
    ck = report["checkpoint"]
    assert ck["param_maxdiff"] == 0.0, ck   # exact: save/restore, no math
    assert ck["moment_maxdiff"] == 0.0, ck
    assert ck["shardings_match"]
    assert ck["post_restore_step"] == 3
    assert ck["post_restore_loss_finite"]


# ---------------------------------------------------------------------------
# preemption / fault injection (crash_resume scenario)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["mid_training", "mid_save"])
def test_crash_leaves_directory_consistent(report, case):
    """After a SIGKILL — including one landing mid-save — LATEST must name a
    fully written checkpoint (atomic rename means manifest present ⟺
    complete), with the step a multiple of the checkpoint cadence."""
    entry = report["crash_resume"][case]
    assert entry["latest_step"] is not None, entry
    assert entry["latest_step"] % 2 == 0, entry
    assert entry["pointer_names_complete"], entry


def test_mid_save_kill_keeps_previous_checkpoint(report):
    """The kill lands inside the SECOND checkpoint's write, so the first
    (step 2) must stay the latest complete one, and the partial write must
    be visible only as a stray tmp dir."""
    entry = report["crash_resume"]["mid_save"]
    assert entry["latest_step"] == 2, entry
    assert entry["stray_tmp_dirs"] >= 1, entry


@pytest.mark.parametrize("case", ["mid_training", "mid_save"])
def test_resume_same_mesh_is_bit_exact(report, case):
    """A killed run resumed on the same data=8 mesh continues with
    loss/metric history BIT-EXACT vs an uninterrupted reference run, from
    the restored step through the end (full state round-trips: params,
    LAMB moments, step counter, data position)."""
    res = report["crash_resume"][case]["resume_same_mesh"]
    assert res["resumed_rows"] > 0, res
    assert res["steps_match"], res
    assert res["bitexact"], res
    assert res["loss_maxdiff"] == 0.0, res
    assert res["final_step"] == 8, res
    assert res["examples_seen_match"], res


def test_resume_other_mesh_shape(report):
    """The same crashed run resumes on a data=4,model=2 mesh: steps and
    examples_seen exact, loss within the cross-mesh reduction-order
    tolerance used by the equivalence suite."""
    res = report["crash_resume"]["mid_training"]["resume_other_mesh"]
    assert res["steps_match"], res
    assert res["loss_maxdiff"] < LOSS_TOL, res
    assert res["final_step"] == 8, res
    assert res["examples_seen_match"], res


@pytest.mark.parametrize("case", ["mid_training", "mid_save"])
def test_resume_garbage_collects_tmp_dirs(report, case):
    """The resumed run's first save must GC the crashed writer's debris,
    and its own checkpoints must advance LATEST to the final step."""
    res = report["crash_resume"][case]["resume_same_mesh"]
    assert res["tmp_gc_after_resume"], res
    assert res["final_latest_step"] == 8, res


# ---------------------------------------------------------------------------
# numerical faults & preemption (nan_skip / spike_rollback / sigterm_resume)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", ["data=8,model=1", "data=4,model=2"])
def test_nan_skip_matches_clean_run_bitwise(report, mesh):
    """With the non-finite guard on, a NaN-poisoned batch is skipped
    in-graph: params AND moments must be BITWISE equal to a run whose
    stream omits that ordinal — the skip verdict is a global reduction,
    so every device agrees and the select is a true no-op."""
    entry = report["nan_skip"][mesh]
    assert entry["skipped"] == 1, entry
    assert entry["param_maxdiff"] == 0.0, entry
    assert entry["moment_maxdiff"] == 0.0, entry
    assert entry["steps_match"], entry


@pytest.mark.parametrize("mesh", ["data=8,model=1", "data=4,model=2"])
def test_spike_rollback_recovers(report, mesh):
    """An injected loss spike trips the watchdog: exactly one rollback to
    the last validated checkpoint, the suspect window is dropped (step
    arithmetic proves no batch is silently retrained), and the run ends
    ok with finite loss."""
    entry = report["spike_rollback"][mesh]
    assert entry["rollbacks"] == 1, entry
    assert entry["reason"] == "loss_spike", entry
    assert entry["restored_step"] < entry["from_step"], entry
    assert entry["step_arithmetic_ok"], entry
    assert entry["final_loss_finite"], entry
    assert entry["status"] == "ok", entry


def test_sigterm_preemption_resumes_bit_exact(report):
    """SIGTERM mid-run: the victim saves inside the grace window, exits
    cleanly with status=preempted, and a --resume run continues BIT-EXACT
    vs an uninterrupted reference."""
    entry = report["sigterm_resume"]
    assert entry["preempt_status"] == "preempted", entry
    assert entry["stopped_early"], entry
    assert entry["saved_at_preempt_step"], entry
    assert entry["resumed_rows"] > 0, entry
    assert entry["bitexact"], entry
    assert entry["final_step"] == 8, entry
    assert entry["resume_status"] == "ok", entry


def test_fsdp_shrinks_per_device_state_memory(report):
    """Params + LAMB moments per device must shrink ≥4× under data=8 FSDP
    (measured ~8× — replicated scalars keep it from exactly N×)."""
    mem = report["memory"]
    assert mem["state_ratio"] >= 4.0, mem
    cs, cb = mem["compiled_sharded"], mem["compiled_single"]
    if "argument_bytes" in cs and "argument_bytes" in cb:
        # compiled per-device argument footprint (state + batch slice) must
        # shrink too; batch bytes are shared so the bound is looser
        assert cs["argument_bytes"] * 2 < cb["argument_bytes"], mem


def test_non_divisible_batches_raise(report):
    g = report["guards"]
    assert g["pipeline_raises"], g
    assert "divisible" in g["pipeline_msg"]
    assert g["trainer_raises"], g
    assert "divisible" in g["trainer_msg"]


# ---------------------------------------------------------------------------
# in-process unit checks (no devices needed)
# ---------------------------------------------------------------------------

def test_pallas_spec_ok_gates_sharded_leaves():
    from jax.sharding import PartitionSpec as P

    from repro.kernels import pallas_spec_ok

    assert pallas_spec_ok(None)
    assert pallas_spec_ok(P())
    assert pallas_spec_ok(P(None, None))
    assert not pallas_spec_ok(P("data"))
    assert not pallas_spec_ok(P(None, ("pod", "data")))
    assert not pallas_spec_ok(P(None, "model"))


@pytest.mark.parametrize("mode", ["pallas", "interpret"])
def test_fused_lamb_apply_sharded_specs_fall_back_to_xla(mode):
    """Kernel-path modes (pallas AND interpret) with fully sharded specs
    must run on CPU: every leaf takes the per-leaf XLA fallback, so the
    single-device-layout kernel is never launched on a sharded leaf."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels import fused_lamb_apply

    params = {"w": jnp.ones((8, 4)), "b": jnp.zeros((4,))}
    grads = jax.tree.map(jnp.ones_like, params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    specs = {"w": P("data", None), "b": P("data")}
    x_kern, _, _, _ = fused_lamb_apply(
        params, grads, zeros, zeros, jnp.asarray(1), jnp.asarray(1e-3),
        mode=mode, param_specs=specs,
    )
    x_xla, _, _, _ = fused_lamb_apply(
        params, grads, zeros, zeros, jnp.asarray(1), jnp.asarray(1e-3),
        mode="xla",
    )
    for a, b in zip(jax.tree.leaves(x_kern), jax.tree.leaves(x_xla)):
        assert jnp.allclose(a, b)


def test_parse_mesh_spec():
    from repro.launch.mesh import parse_mesh_spec

    assert parse_mesh_spec("data=4,model=2") == {"data": 4, "model": 2}
    assert parse_mesh_spec("pod=2, data=8, model=4") == {
        "pod": 2, "data": 8, "model": 4
    }
    for bad in ("data", "data=x", "data=0", "data=2,data=4", "=4"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


def test_make_host_mesh_rejects_bad_model_parallel():
    from repro.launch.mesh import make_host_mesh

    with pytest.raises(ValueError, match="divisor of"):
        make_host_mesh(3)  # 1 CPU device in-process: 1 % 3 != 0


def test_mesh_spec_too_many_devices():
    from repro.launch.mesh import make_mesh_from_spec

    with pytest.raises(ValueError, match="devices"):
        make_mesh_from_spec("data=64,model=64")


def test_train_state_shardings_structure():
    """Moments mirror their parameter's sharding; scalars replicate."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import TrainConfig
    from repro.models import build_model
    from repro.sharding import train_state_shardings
    from repro.train.step import make_train_step

    from tests.conftest import tiny_dense

    model = build_model(tiny_dense())
    tc = TrainConfig(optimizer="lamb", use_fused_lamb=True)
    init_fn, _ = make_train_step(model, tc)
    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ssh = train_state_shardings(model.defs, abstract, mesh)
    assert ssh.step.spec == P()
    assert ssh.opt_state.count.spec == P()
    assert ssh.opt_state.mu["embed"] == ssh.params["embed"]
    assert ssh.opt_state.nu["blocks"]["attn"]["wq"] == (
        ssh.params["blocks"]["attn"]["wq"]
    )
