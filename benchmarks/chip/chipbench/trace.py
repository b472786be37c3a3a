"""Device traces: capture with the JAX profiler, extraction of the events
the per-layer metrics read, and the reductions they share.

The extracted trace is plain data (and what the tests run on):

    {"window": [t0_ns, t1_ns],            # the traced span, host clock
     "devices": {"0": {"ops": [[name, start_ns, dur_ns, path], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}, ...},
     "host": [[name, start_ns, dur_ns], ...]}  # the benchmark's own spans

``name`` is the HLO instruction's name (``fusion.12``, ``copy.3``); a
Pallas kernel's custom call is named after the kernel (``flash_fwd.2``).
``path`` is the op's name stack where the trace carries one (``tf_op``),
else empty.  The "XLA Ops" line nests a loop's body inside the loop's own
event (``while``); a union of intervals counts such time once, and the
per-op breakdown and the collectives leave the containers out.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CONTAINERS = ("while", "conditional", "call")
HLO_NAME = re.compile(r"^%?([^\s=]+)")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce|reducescatter)", re.I)


# ---------------------------------------------------------------------------
# capture and extraction
# ---------------------------------------------------------------------------

def latest_xplane(outdir: str) -> str:
    found = sorted(glob.glob(os.path.join(outdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no profiler trace under {outdir}")
    return found[-1]


def _stat(event, keys: Sequence[str]) -> str:
    for k, v in event.stats:
        if k in keys and isinstance(v, str):
            return v
    return ""


def op_name(text: str) -> str:
    """The instruction name from an op event's HLO text."""
    m = HLO_NAME.match(text)
    return m.group(1) if m else text


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def extract(xplane_path: str) -> dict:
    """The events the metrics read, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    window = [s for s in host if s[0] == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    t0, t1 = window[0][1], window[0][1] + window[0][2]

    def inside(e) -> bool:
        return e.start_ns < t1 and e.start_ns + e.duration_ns > t0

    devices: Dict[str, dict] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = devices.setdefault(m.group(1), {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == "XLA Ops":
                dev["ops"] += [[op_name(e.name), e.start_ns, e.duration_ns,
                                _stat(e, ("tf_op",))]
                               for e in line.events if inside(e)]
            elif line.name == "XLA Modules":
                dev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if inside(e)]
    return {"window": [t0, t1], "devices": devices,
            "host": [h for h in host if h[1] < t1 and h[1] + h[2] > t0]}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

Interval = Tuple[float, float]


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def op_intervals(ops, keep=lambda op: True) -> List[Interval]:
    return [(op[1], op[1] + op[2]) for op in ops if keep(op)]


def busy_ns(trace: dict, dev: str) -> float:
    t0, t1 = trace["window"]
    return length(union(clip(op_intervals(trace["devices"][dev]["ops"]),
                             t0, t1)))


def busy_s(trace: dict) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    devs = list(trace["devices"])
    return sum(busy_ns(trace, d) for d in devs) / max(len(devs), 1) / 1e9


def window_s(trace: dict) -> float:
    t0, t1 = trace["window"]
    return (t1 - t0) / 1e9


def idle_share(trace: dict) -> Optional[float]:
    """1 - busy / window, in %, averaged over devices; None without ops."""
    if not trace["devices"] or not any(d["ops"] for d in
                                       trace["devices"].values()):
        return None
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def subtract(a: List[Interval], b: List[Interval]) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def is_container(op) -> bool:
    return base_name(op[0]) in CONTAINERS


def is_collective(op) -> bool:
    """A collective by its name, or by its HLO category where the op row
    carries one (``program_trace``): the compiler names a reduce-scatter
    it fused ``fusion.N``, of category ``all-reduce-scatter fusion``."""
    return bool(COLLECTIVE.match(op[0])
                or (len(op) > 4 and COLLECTIVE.match(op[4])))


def is_kernel(op, kernel: str) -> bool:
    """A Pallas kernel is found by its stable name: the custom call's own
    name, or its name stack where the trace has one."""
    return base_name(op[0]) == kernel or f"/{kernel}/" in op[3]


def kernel_events(trace: dict, kernel: str) -> List[list]:
    t0, t1 = trace["window"]
    return [op for d in trace["devices"].values() for op in d["ops"]
            if is_kernel(op, kernel) and t0 <= op[1] < t1]


def module_events(trace: dict, pattern: str) -> List[list]:
    t0, t1 = trace["window"]
    rx = re.compile(pattern)
    return [m for d in trace["devices"].values() for m in d["modules"]
            if rx.search(m[0]) and t0 <= m[1] < t1]


def exposed_collective_ns(trace: dict, dev: str) -> float:
    """Time of collective ops on ``dev`` during which no other op runs."""
    t0, t1 = trace["window"]
    ops = trace["devices"][dev]["ops"]
    coll = clip(op_intervals(ops, is_collective), t0, t1)
    comp = clip(op_intervals(ops, lambda o: not (is_collective(o)
                                                 or is_container(o))), t0, t1)
    return subtract(coll, comp)


def top_ops(trace: dict, n: int = 10) -> List[list]:
    """The device ops that took the most time, summed by name, in seconds
    averaged over devices."""
    t0, t1 = trace["window"]
    tot: Dict[str, float] = {}
    for d in trace["devices"].values():
        for op in d["ops"]:
            if t0 <= op[1] < t1 and not is_container(op):
                tot[op[0]] = tot.get(op[0], 0.0) + op[2]
    k = max(len(trace["devices"]), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """The longest gaps on the first device with no op running, named by
    the innermost benchmark span open at the gap's middle."""
    t0, t1 = trace["window"]
    if not trace["devices"]:
        return []
    dev = sorted(trace["devices"], key=int)[0]
    busy = union(clip(op_intervals(trace["devices"][dev]["ops"]), t0, t1))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    spans = [s for s in trace["host"] if s[0] != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        open_ = [s for s in spans if s[1] <= mid < s[1] + s[2]]
        name = min(open_, key=lambda s: s[2])[0] if open_ else "none"
        out.append([name[len(SPAN_PREFIX):] if name.startswith(SPAN_PREFIX)
                    else name, (b - a) / 1e9])
    return out
