"""Observability: in-graph numerics counters, step tracing, metric sinks.

Import discipline: this package must not import ``repro.core`` (core ops
import *it* for the tap hooks) — only jax + stdlib.
"""
from .metrics import (
    DHIST_EDGES,
    NumericsCollector,
    collecting,
    current_scope,
    dhist_edges_codes,
    enabled,
    observe_codes,
    observe_convert,
    observe_float,
    observe_quantize,
    scope,
    scope_active,
    suspended,
    tap,
)
from .registry import MetricsRegistry
from .sink import JsonlSink, read_jsonl, read_jsonl_tolerant
from .trace import (
    StepTimer,
    host_span,
    maybe_profile,
    phase_scope,
)

__all__ = [
    "DHIST_EDGES",
    "NumericsCollector",
    "collecting",
    "current_scope",
    "dhist_edges_codes",
    "enabled",
    "observe_codes",
    "observe_convert",
    "observe_float",
    "observe_quantize",
    "scope",
    "scope_active",
    "suspended",
    "tap",
    "MetricsRegistry",
    "JsonlSink",
    "read_jsonl",
    "read_jsonl_tolerant",
    "StepTimer",
    "host_span",
    "maybe_profile",
    "phase_scope",
]
