"""Async-dispatch-aware span timers.

JAX dispatch is asynchronous: ``t1 - t0`` around a jit'd call measures
Python dispatch, not device work, so naive per-step timing *lies* — the
first timed step absorbs compilation and every later one reads near zero
while the device queue runs behind.  A :class:`SpanRecorder` span therefore
synchronizes only at its *boundaries*: ``block_until_ready`` on the
arrays handed to ``sync=`` when the span opens (drain the queue of prior
work) and on whatever the body registered via ``handle.block_on(...)``
when it closes (wait for the span's own work).  Everything dispatched
inside the span overlaps freely, so timing k steps costs two syncs, not k.

Two usage shapes share one accumulator:

* scoped::

      with spans.span("step", sync=state) as sp:
          for _ in range(k):
              state, metrics = step(state, batch)
          sp.block_on(state)
          sp.count = k

* phase-style (loop bodies that decide boundaries mid-iteration)::

      spans.start("step", sync=state)
      ...
      spans.stop("step", sync=(state, metrics), count=k)

Each closed span is one observation (``seconds`` / ``count`` items);
``summary()`` folds observations into count/total/mean/p50/max per name,
and a wired :class:`~repro.telemetry.events.EventLog` receives one
``span`` event per close.

Profiler spans.  :func:`trace_span` names a stretch of host work in a
``jax.profiler`` trace, on the clock the device's ops are recorded on, so a
gap on the device reads as the host work that was running in it.  Every
recorder span opens one too (``span.`` + its name, ``count`` as an arg).
With no profiler running a span costs a TraceMe check and one Python
object: nothing is formatted and nothing syncs.  :func:`compile_count` is
the process's running count of XLA compilations (persistent-cache hits
excluded), fed by ``jax.monitoring``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.telemetry.events import EventLog

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_PREFIX = "span."  # a recorder span ``step`` is ``span.step`` in a trace
_compiles = 0


def _on_duration(event: str, duration: float, **_) -> None:
    global _compiles
    if event == COMPILE_EVENT:
        _compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count() -> int:
    """XLA compilations in this process so far (cache hits excluded)."""
    return _compiles


def trace_span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span in the profiler's trace, with ``args`` as its stats::

        with trace_span("serve.admit", rid=req.rid) as sp:
            ...
            sp.set_metadata(slot=slot)   # args known only later
    """
    return jax.profiler.TraceAnnotation(name, **args)


class SpanHandle:
    """Mutable per-span state the body can attach results to."""

    def __init__(self, count: int = 1):
        self.count = count
        self._pending: List[Any] = []

    def block_on(self, tree: Any) -> Any:
        """Register arrays the span must wait for at close (returns them)."""
        self._pending.append(tree)
        return tree


class SpanRecorder:
    """Accumulates named span observations; optionally emits span events."""

    def __init__(self, log: Optional[EventLog] = None):
        self.log = log
        self._obs: Dict[str, List[tuple]] = {}  # name -> [(seconds, count)]
        self._open: Dict[str, tuple] = {}  # name -> (t0, open trace span)
        self._trace_names: Dict[str, str] = {}

    def _trace_span(self, name: str) -> jax.profiler.TraceAnnotation:
        full = self._trace_names.get(name)
        if full is None:
            full = self._trace_names[name] = TRACE_PREFIX + name
        return trace_span(full)

    # -- core ----------------------------------------------------------------
    def observe(self, name: str, seconds: float, count: int = 1) -> None:
        """Record one closed span (the single accumulation point)."""
        self._obs.setdefault(name, []).append((float(seconds), int(count)))
        if self.log is not None:
            self.log.emit("span", name=name, seconds=float(seconds),
                          count=int(count))

    @staticmethod
    def _sync(tree: Any) -> None:
        if tree is not None:
            jax.block_until_ready(tree)

    # -- scoped --------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, *, sync: Any = None, count: int = 1):
        self._sync(sync)
        handle = SpanHandle(count)
        with self._trace_span(name) as traced:
            t0 = time.perf_counter()
            try:
                yield handle
            finally:
                for tree in handle._pending:
                    self._sync(tree)
                traced.set_metadata(count=handle.count)
                self.observe(name, time.perf_counter() - t0, handle.count)

    # -- phase-style ---------------------------------------------------------
    def start(self, name: str, *, sync: Any = None) -> None:
        """Open (or re-open) a named span; syncs, then stamps t0."""
        stale = self._open.pop(name, None)
        if stale is not None:  # re-opened: the old trace span ends here
            stale[1].__exit__(None, None, None)
        self._sync(sync)
        traced = self._trace_span(name)
        traced.__enter__()
        self._open[name] = (time.perf_counter(), traced)

    def stop(self, name: str, *, sync: Any = None, count: int = 1) -> float:
        """Close a named span opened by :meth:`start`; returns seconds."""
        opened = self._open.pop(name, None)
        if opened is None:
            raise ValueError(f"span {name!r} was never started")
        t0, traced = opened
        self._sync(sync)
        dt = time.perf_counter() - t0
        traced.set_metadata(count=count)
        traced.__exit__(None, None, None)
        self.observe(name, dt, count)
        return dt

    # -- aggregation ---------------------------------------------------------
    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name {count, total_s, mean_s, p50_s, max_s}; ``mean_s`` is
        per *item* (seconds/count), so a 10-step span contributes per-step
        time — the number to compare across log cadences."""
        out = {}
        for name, obs in self._obs.items():
            secs = np.array([s for s, _ in obs])
            items = np.array([c for _, c in obs])
            per_item = secs / np.maximum(items, 1)
            out[name] = {
                "count": int(items.sum()),
                "total_s": float(secs.sum()),
                "mean_s": float(secs.sum() / max(items.sum(), 1)),
                "p50_s": float(np.percentile(per_item, 50)),
                "max_s": float(per_item.max()),
            }
        return out
