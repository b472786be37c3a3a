"""Unified telemetry: structured events, span timers, trust-ratio
recording, and the regression-gated run report.

See docs/observability.md for the walkthrough.
"""
from repro.telemetry.events import (
    EVENT_TYPES,
    SCHEMA_VERSION,
    EventLog,
    config_hash,
    read_events,
    run_provenance,
    validate_event,
)
from repro.telemetry.report import Check, CompareResult, RunReport
from repro.telemetry.spans import SpanRecorder, compile_count, trace_span
from repro.telemetry.trust import HIST_EDGES, PER_LAYER_KEY, TrustRecorder, leaf_names

__all__ = [
    "Check",
    "CompareResult",
    "EVENT_TYPES",
    "EventLog",
    "HIST_EDGES",
    "PER_LAYER_KEY",
    "RunReport",
    "SCHEMA_VERSION",
    "SpanRecorder",
    "TrustRecorder",
    "compile_count",
    "config_hash",
    "leaf_names",
    "read_events",
    "run_provenance",
    "trace_span",
    "validate_event",
]
