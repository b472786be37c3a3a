"""The program's own spans and scopes, re-read from a traced run's xplane.

``trace.extract`` keeps only the benchmark's ``bench:`` host spans, and its
ops carry no name stack.  The readers of the program's spans
(``serve.*``, ``train.*``, ``data.*``, ``repro.telemetry.trace_span``) and
of its named scopes read the cell's xplane again: it still lies under
``.traces/<cell>`` while the per-layer metrics are read.  It is parsed once
a run and kept on the ``Ctx``, as plain data (what the tests run on):

    {"window": [t0_ns, t1_ns],                 # the traced span, host clock
     "spans": [[name, start_ns, dur_ns, {arg: value}], ...],
     "devices": {"0": {"ops": [[name, start_ns, dur_ns, scope, category],
                               ...],
                       "modules": [[name, start_ns, dur_ns], ...]}, ...}}

``scope`` is the op's name stack (``jit(step_fn)/transpose(jvp(model))/
...``): on a TPU v5e it is the ``tf_op`` stat of the op's event metadata,
which ``ProfileData`` does not expose, so ``op_stats`` reads the xplane's
protobuf for it.  An op the compiler made (a layout copy) has none.
``category`` is the ``hlo_category`` stat of the same metadata (``loop
fusion``, ``all-gather``, ``collective-permute-done``): a reduce-scatter
the compiler fused is a ``fusion.N`` known by its category alone
(``all-reduce-scatter fusion``).
A program without the spans or scopes gives no number: every reduction
here returns None then.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

from chipbench import BENCH_DIR
from chipbench import trace as tr

PROGRAM_SPAN = re.compile(r"^(serve|train|data|span)\.")
# the step's parts; a Pallas call is placed by its kernel's name
KERNEL_PART = {"flash_fwd": "forward", "fused_ce_fwd": "forward",
               "flash_dq": "backward", "flash_dkv": "backward",
               "fused_ce_dh": "backward", "fused_ce_dw": "backward",
               "lamb_moments": "optimizer", "lamb_apply": "optimizer"}
SCOPE_PART = [(re.compile(r"transpose\(jvp\(model\)\)"), "backward"),
              (re.compile(r"(^|/)(jvp\()?model(\))?(/|$)"), "forward"),
              (re.compile(r"(^|/)optimizer(/|$)"), "optimizer"),
              (re.compile(r"(^|/)cast_params(/|$)"), "cast")]
PARTS = ("forward", "backward", "optimizer", "cast")
STEP_PROGRAM = re.compile(r"^jit_step_fn")   # Trainer's jitted ``step_fn``
DECODE_PROGRAM = re.compile(r"^jit_step\b")  # the engine's decode ``step``


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def _xplane_messages():
    """Message classes for the part of the profiler's ``XSpace`` proto
    (tsl/profiler/protobuf/xplane.proto) that holds the ops' metadata
    stats, which ``jax.profiler.ProfileData`` does not expose.  A map
    field is read as the repeated entry message it is on the wire."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": f.TYPE_INT64, "uint64": f.TYPE_UINT64,
             "string": f.TYPE_STRING}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    layout = {
        "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
                  ("ref_value", 7, "uint64")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                           ("stats", 5, "*XStat")],
        "EventMetadataEntry": [("key", 1, "int64"),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, "string"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    for name, fields in layout.items():
        msg = fdp.message_type.add(name=name)
        for fname, number, kind in fields:
            many = kind.startswith("*")
            kind = kind.lstrip("*")
            fd = msg.field.add(name=fname, number=number,
                               label=f.LABEL_REPEATED if many
                               else f.LABEL_OPTIONAL)
            if kind in kinds:
                fd.type = kinds[kind]
            else:
                fd.type, fd.type_name = f.TYPE_MESSAGE, \
                    f".chipbench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


OP_STATS = ("tf_op", "hlo_category")


def op_stats(xplane_path: str) -> Dict[str, Dict[str, Dict[str, str]]]:
    """{device plane: {op event name: {stat: value}}}: the ``tf_op`` (its
    HLO ``op_name``) and ``hlo_category`` stats of each op's event
    metadata, a referenced stat resolved, where it has them."""
    space = _xplane_messages()()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for plane in space.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        ops = out.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            md = entry.value
            for st in md.stats:
                name = stat_names.get(st.metadata_id)
                if name in OP_STATS:
                    value = st.str_value or stat_names.get(st.ref_value, "")
                    if value and not ops.get(md.name, {}).get(name):
                        ops.setdefault(md.name, {})[name] = value.rstrip(":")
    return out


def read(xplane_path: str, window) -> dict:
    """Program spans and scoped device ops of one ``.xplane.pb`` that
    overlap ``window``."""
    from jax.profiler import ProfileData

    t0, t1 = window

    def inside(e) -> bool:
        return e.start_ns < t1 and e.start_ns + e.duration_ns > t0

    spans: List[list] = []
    devices: Dict[str, dict] = {}
    stats = op_stats(xplane_path)
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if PROGRAM_SPAN.match(e.name) and inside(e):
                        args = {k: v for k, v in e.stats
                                if not k.startswith("_")}
                        spans.append([e.name, e.start_ns, e.duration_ns,
                                      args])
            continue
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = devices.setdefault(m.group(1), {"ops": [], "modules": []})
        meta = stats.get(plane.name, {})
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    if inside(e):
                        st = meta.get(e.name, {})
                        dev["ops"].append([tr.op_name(e.name), e.start_ns,
                                           e.duration_ns, st.get("tf_op", ""),
                                           st.get("hlo_category", "")])
            elif line.name == "XLA Modules":
                dev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if inside(e)]
    spans.sort(key=lambda s: s[1])
    return {"window": [t0, t1], "spans": spans, "devices": devices}


def load(ctx) -> Optional[dict]:
    """The run's program trace, parsed once and kept on ``ctx``; None
    where the run kept no trace."""
    if not hasattr(ctx, "program_trace"):
        pt = None
        outdir = BENCH_DIR / ".traces" / ctx.cell.name
        if ctx.trace is not None and outdir.is_dir():
            try:
                path = tr.latest_xplane(str(outdir))
            except FileNotFoundError:
                path = None
            if path is not None:
                pt = read(path, ctx.trace["window"])
        ctx.program_trace = pt
    return ctx.program_trace


# ---------------------------------------------------------------------------
# the training step's parts
# ---------------------------------------------------------------------------

def scope_part(scope: str) -> str:
    """The part the program's named scopes put an op in, or ''."""
    for rx, name in SCOPE_PART:
        if rx.search(scope):
            return name
    return ""


def step_part(op) -> str:
    """forward | backward | optimizer | cast, or '' for an op of none."""
    return KERNEL_PART.get(tr.base_name(op[0])) or scope_part(op[3])


def step_parts_ms(pt: Optional[dict]) -> Optional[dict]:
    """Device ms a step of each part, of the ops inside the window's step
    programs, with ``busy`` (their union) and ``other`` (busy less the
    parts), mean over chips.  None without step programs, or where no op
    carries one of the program's scopes (a name stack alone, as every op
    has one, does not do)."""
    if not pt:
        return None
    t0, t1 = pt["window"]
    per_dev = []
    scoped = False
    for dev in pt["devices"].values():
        steps = sorted((m[1], m[1] + m[2]) for m in dev["modules"]
                       if STEP_PROGRAM.match(m[0]) and t0 <= m[1]
                       and m[1] + m[2] <= t1)
        if not steps:
            continue
        starts = np.array([a for a, _ in steps])
        ends = np.array([b for _, b in steps])
        sums = dict.fromkeys(PARTS, 0.0)
        busy = []
        for op in dev["ops"]:
            if tr.is_container(op):
                continue
            j = int(np.searchsorted(starts, op[1], side="right")) - 1
            if j < 0 or op[1] + op[2] > ends[j]:
                continue
            busy.append((op[1], op[1] + op[2]))
            scoped = scoped or bool(scope_part(op[3]))
            part = step_part(op)
            if part:
                sums[part] += op[2]
        sums["busy"] = tr.length(tr.union(busy))
        sums["other"] = sums["busy"] - sum(sums[p] for p in PARTS)
        per_dev.append({k: v / len(steps) / 1e6 for k, v in sums.items()})
    if not per_dev or not scoped:
        return None
    return {k: float(np.mean([d[k] for d in per_dev])) for k in per_dev[0]}


# ---------------------------------------------------------------------------
# serving: idle time named by the engine's spans
# ---------------------------------------------------------------------------

def _first_device(pt: dict) -> Optional[dict]:
    if not pt["devices"]:
        return None
    return pt["devices"][sorted(pt["devices"], key=int)[0]]


def _busy(dev: dict) -> List[tr.Interval]:
    return tr.union(tr.op_intervals(dev["ops"]))


def spans_named(pt: dict, name: str, whole: bool = False) -> List[list]:
    """Program spans called ``name`` that start in the window (``whole``:
    that lie wholly in it)."""
    t0, t1 = pt["window"]
    return [s for s in pt["spans"] if s[0] == name and t0 <= s[1] < t1
            and (not whole or s[1] + s[2] <= t1)]


def admit_idle_ms(pt: Optional[dict]) -> Optional[float]:
    """Median over the admissions wholly in the window of the admission's
    length less the device's busy time inside it."""
    if not pt:
        return None
    dev = _first_device(pt)
    admits = spans_named(pt, "serve.admit", whole=True)
    if dev is None or not admits:
        return None
    busy = _busy(dev)
    idle = [s[2] - tr.length(tr.clip(busy, s[1], s[1] + s[2]))
            for s in admits]
    return float(np.median(idle)) / 1e6


def turn_host_ms(pt: Optional[dict]) -> Optional[float]:
    """Median over consecutive decode programs (``jit_step``) of the
    device-idle time between them that no admission covers."""
    if not pt or not spans_named(pt, "serve.decode"):
        return None
    dev = _first_device(pt)
    if dev is None:
        return None
    t0, t1 = pt["window"]
    steps = sorted((m[1], m[1] + m[2]) for m in dev["modules"]
                   if DECODE_PROGRAM.match(m[0]) and t0 <= m[1]
                   and m[1] + m[2] <= t1)
    if len(steps) < 2:
        return None
    covered = _busy(dev) + [(s[1], s[1] + s[2])
                            for s in pt["spans"] if s[0] == "serve.admit"]
    gaps = [tr.subtract([(a[1], b[0])], covered)
            for a, b in zip(steps, steps[1:]) if b[0] > a[1]]
    return float(np.median(gaps)) / 1e6 if gaps else None


def prefills_per_step(pt: Optional[dict]) -> Optional[float]:
    """Admissions over decode steps, of the spans that start in the
    window."""
    if not pt:
        return None
    steps = spans_named(pt, "serve.decode")
    if not steps:
        return None
    return len(spans_named(pt, "serve.admit")) / len(steps)
