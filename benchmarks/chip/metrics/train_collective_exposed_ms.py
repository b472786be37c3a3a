"""Device ms a training step spends in collectives (all-gather,
reduce-scatter, all-reduce, collective-permute, their async ``-start`` and
``-done`` parts included) while no other op runs on that chip: the
exchange the step waits for, per ``jit_step_fn`` in the window, mean over
the chips.  An op is a collective by its name or by its HLO category, as
the program trace carries it (chipbench.program_trace): the compiler names
a reduce-scatter it fused ``fusion.N``.  Silent where the trace holds no
collective (one chip)."""
from chipbench import program_trace as pt
from chipbench import trace


def read(ctx):
    t = pt.load(ctx)
    if not t:
        return None
    per_chip = []
    for dev, d in t["devices"].items():
        steps = trace.module_events(
            {"window": t["window"], "devices": {dev: d}}, r"^jit_step_fn")
        if steps and any(trace.is_collective(op) for op in d["ops"]):
            per_chip.append(trace.exposed_collective_ns(t, dev)
                            / len(steps) / 1e6)
    return sum(per_chip) / len(per_chip) if per_chip else None
