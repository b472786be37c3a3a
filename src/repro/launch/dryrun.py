import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Test hook only: REPRO_DRYRUN_DEVICES=8 shrinks the fake device pool (the
# production dry-run always uses the 512 set above).  Still before jax import.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh, with NO array allocation (ShapeDtypeStruct inputs), and
extract memory / cost / collective roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
        --shape train_4k [--multi-pod] [--out results.jsonl] [--set remat=full]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, get_shape, plan  # noqa: E402
from repro.configs.base import InputShape, ModelConfig, TrainConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import analyze, model_flops  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve.engine import make_decode_step, make_prefill_step  # noqa: E402
from repro.sharding import (  # noqa: E402
    ShardCtx,
    batch_shardings,
    cache_shardings,
    default_act_rules,
    opt_state_shardings,
    resolve_spec,
    shardings_for,
    use_sharding,
)
from repro.train.step import TrainState, make_optimizer, make_train_step  # noqa: E402


# Placement trees (batch_shardings / cache_shardings / opt_state_shardings)
# live in repro.sharding.placement — shared with the real Trainer path, so
# the layouts this dry-run compiles are the layouts training runs.

# ---------------------------------------------------------------------------
# step builders: (fn, abstract args, in_shardings, donate)
# ---------------------------------------------------------------------------

def build_train(model, shape: InputShape, mesh, rules, optimizer: str,
                param_rules=None, tc_kw=None):
    tc = TrainConfig(optimizer=optimizer, learning_rate=1e-3, **(tc_kw or {}))
    opt = make_optimizer(model, tc)
    _, step_fn = make_train_step(model, tc, optimizer=opt)

    aparams = model.abstract_params()
    aopt = jax.eval_shape(opt.init, aparams)
    counter = jax.ShapeDtypeStruct((), jnp.int32)
    astate = TrainState(aparams, aopt, counter, counter)
    abatch = model.input_specs(shape)

    psh = shardings_for(model.defs, mesh, param_rules)
    osh = opt_state_shardings(aopt, psh, mesh)
    ssh = TrainState(psh, osh, NamedSharding(mesh, P()),
                     NamedSharding(mesh, P()))
    bsh = batch_shardings(abatch, mesh, rules)

    def wrapped(state, batch):
        new_state, metrics = step_fn(state, batch)
        # keep the output state resident where the input state lives
        new_state = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s), new_state, ssh
        )
        return new_state, metrics

    return wrapped, (astate, abatch), (ssh, bsh), (0,)


def build_prefill(model, shape: InputShape, mesh, rules, param_rules=None):
    fn = make_prefill_step(model)
    aparams = model.abstract_params()
    abatch = model.input_specs(shape)
    acache = model.make_cache(shape.global_batch, shape.seq_len, abstract=True)
    psh = shardings_for(model.defs, mesh, param_rules)
    bsh = batch_shardings(abatch, mesh, rules)
    csh = cache_shardings(acache, mesh, rules)
    return fn, (aparams, abatch, acache), (psh, bsh, csh), (2,)


def build_decode(model, shape: InputShape, mesh, rules, param_rules=None):
    fn = make_decode_step(model)
    b = shape.global_batch
    aparams = model.abstract_params()
    acache = model.make_cache(b, shape.seq_len, abstract=True)
    atok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    apos = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    psh = shardings_for(model.defs, mesh, param_rules)
    csh = cache_shardings(acache, mesh, rules)
    tsh = NamedSharding(mesh, resolve_spec((b, 1), ("batch", None), rules, mesh))
    return fn, (aparams, acache, atok, apos), (psh, csh, tsh, tsh), (1,)


def build_encoder_forward(model, shape: InputShape, mesh, rules):
    """Encoder 'prefill' = plain forward (no cache)."""

    def fn(params, batch):
        logits, _ = model.apply(params, batch)
        return logits[:, -1]

    aparams = model.abstract_params()
    abatch = model.input_specs(shape)
    psh = shardings_for(model.defs, mesh)
    bsh = batch_shardings(abatch, mesh, rules)
    return fn, (aparams, abatch), (psh, bsh), ()


# ---------------------------------------------------------------------------
# main runner
# ---------------------------------------------------------------------------

def _mem_dict(mem) -> Dict[str, float]:
    out = {}
    for attr in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes", "peak_memory_in_bytes",
    ):
        try:
            out[attr] = int(getattr(mem, attr))
        except (AttributeError, TypeError):
            pass
    return out


def apply_overrides(cfg: ModelConfig, sets) -> ModelConfig:
    for item in sets or []:
        key, _, val = item.partition("=")
        cur = getattr(cfg, key)
        if isinstance(cur, bool):
            parsed: Any = val.lower() in ("1", "true", "yes")
        elif cur is None:
            parsed = None if val.lower() == "none" else int(val)
        elif isinstance(cur, int):
            parsed = int(val)
        elif isinstance(cur, float):
            parsed = float(val)
        else:
            parsed = val
        cfg = cfg.replace(**{key: parsed})
    return cfg


def run_dryrun(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    optimizer: str = "lamb",
    sets=None,
    mesh=None,
    act_rule_sets=None,
    param_rule_sets=None,
    moment_dtype: Optional[str] = None,
    tag: str = "",
) -> Dict[str, Any]:
    shape = get_shape(shape_name)
    cfg0 = get_config(arch)
    cfg, note = plan(cfg0, shape)
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2pod" if multi_pod else "1pod",
        "optimizer": optimizer, "note": note, "tag": tag,
        "overrides": list(sets or []),
        "act_rules": list(act_rule_sets or []),
        "param_rules": list(param_rule_sets or []),
        "moment_dtype": moment_dtype,
    }
    if cfg is None:
        record["status"] = "skipped"
        return record
    cfg = apply_overrides(cfg, sets)

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rules = default_act_rules(multi_pod="pod" in mesh.shape)
    rules["cache_seq"] = ("pod", "data")
    rules["inner"] = ("model",)
    for item in act_rule_sets or []:
        k, _, v = item.partition("=")
        rules[k] = tuple(x for x in v.split(",") if x) or None

    param_rules = None
    if param_rule_sets:
        from repro.sharding import default_param_rules

        param_rules = default_param_rules(multi_pod="pod" in mesh.shape)
        for item in param_rule_sets:
            k, _, v = item.partition("=")
            param_rules[k] = tuple(x for x in v.split(",") if x) or None
    tc_kw = {"moment_dtype": moment_dtype} if moment_dtype else {}

    model = build_model(cfg)
    if shape.kind == "train":
        builder = lambda: build_train(model, shape, mesh, rules, optimizer,
                                      param_rules, tc_kw)
    elif shape.kind == "prefill":
        builder = (
            (lambda: build_encoder_forward(model, shape, mesh, rules))
            if cfg.is_encoder
            else (lambda: build_prefill(model, shape, mesh, rules, param_rules))
        )
    else:
        builder = lambda: build_decode(model, shape, mesh, rules, param_rules)

    ctx = ShardCtx(mesh, rules)
    t0 = time.perf_counter()
    with use_sharding(ctx):
        fn, args, in_sh, donate = builder()
        lowered = jax.jit(
            fn, in_shardings=in_sh, donate_argnums=donate
        ).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    cost = dict(compiled.cost_analysis() or {})
    try:
        mem = _mem_dict(compiled.memory_analysis())
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    hlo = compiled.as_text()
    cost_source = "scanned"

    # XLA cost analysis counts while-loop (lax.scan) bodies ONCE regardless of
    # trip count, so FLOPs/bytes/collectives of scanned stacks are undercounted
    # by ~n_layers.  Re-lower the mathematically identical UNROLLED variant
    # purely for cost accounting (memory/compile stats above stay from the
    # production scanned artifact).
    if cfg.scan_layers and not os.environ.get("REPRO_DRYRUN_NO_UNROLL"):
        try:
            model_u = build_model(cfg.replace(scan_layers=False))
            with use_sharding(ctx):
                if shape.kind == "train":
                    fn_u, args_u, sh_u, dn_u = build_train(
                        model_u, shape, mesh, rules, optimizer,
                        param_rules, tc_kw)
                elif shape.kind == "prefill":
                    fn_u, args_u, sh_u, dn_u = (
                        build_encoder_forward(model_u, shape, mesh, rules)
                        if cfg.is_encoder
                        else build_prefill(model_u, shape, mesh, rules,
                                           param_rules)
                    )
                else:
                    fn_u, args_u, sh_u, dn_u = build_decode(
                        model_u, shape, mesh, rules, param_rules)
                compiled_u = jax.jit(
                    fn_u, in_shardings=sh_u, donate_argnums=dn_u
                ).lower(*args_u).compile()
            cost = dict(compiled_u.cost_analysis() or {}) or cost
            hlo = compiled_u.as_text()
            cost_source = "unrolled"
        except Exception as e:  # pragma: no cover — fall back to scanned cost
            cost_source = f"scanned (unrolled failed: {type(e).__name__})"

    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    mf = model_flops(shape.kind, model.active_param_count(), tokens) / n_dev
    rf = analyze(cost, hlo, model_flops_per_device=mf)

    record.update(
        status="ok",
        devices=n_dev,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        params=model.param_count(),
        active_params=model.active_param_count(),
        tokens=tokens,
        memory=mem,
        cost={k: cost.get(k) for k in ("flops", "bytes accessed",
                                       "bytes accessed output") if k in cost},
        roofline=rf.to_dict(),
        cost_source=cost_source,
        hlo_lines=hlo.count("\n"),
    )
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimizer", default="lamb")
    ap.add_argument("--set", action="append", default=[],
                    help="model-config override key=value (repeatable)")
    ap.add_argument("--act-rule", action="append", default=[],
                    help="activation sharding rule override name=axis1,axis2")
    ap.add_argument("--param-rule", action="append", default=[],
                    help="parameter sharding rule override name=axis1,axis2 "
                         "(empty value replicates that logical axis)")
    ap.add_argument("--moment-dtype", default="",
                    help="optimizer moment dtype override (e.g. bfloat16)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    rec = run_dryrun(
        args.arch, args.shape, multi_pod=args.multi_pod,
        optimizer=args.optimizer, sets=args.set,
        act_rule_sets=args.act_rule, param_rule_sets=args.param_rule,
        moment_dtype=args.moment_dtype or None, tag=args.tag,
    )
    if rec.get("status") == "ok":
        rl = rec["roofline"]
        print(f"== {args.arch} × {args.shape} × {rec['mesh']} "
              f"[{rec['optimizer']}] ==")
        print(f"  lower {rec['lower_s']}s compile {rec['compile_s']}s  "
              f"hlo_lines {rec['hlo_lines']}")
        print(f"  memory_analysis: {json.dumps(rec['memory'])}")
        print(f"  cost_analysis:   {json.dumps(rec['cost'])}")
        print(f"  compute {rl['compute_s']*1e3:.3f}ms  memory "
              f"{rl['memory_s']*1e3:.3f}ms  collective "
              f"{rl['collective_s']*1e3:.3f}ms  → {rl['dominant']}-bound  "
              f"useful-FLOP {rl['useful_fraction']:.3f}")
    else:
        print(f"== {args.arch} × {args.shape}: {rec['status']} ({rec['note']})")
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
