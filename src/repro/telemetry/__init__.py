"""Zero-dependency observability subsystem (DESIGN.md §Telemetry).

Three layers:

* :mod:`repro.telemetry.drift` — in-jit drift diagnostics, scalar
  reductions computed inside the round functions (cost: a few f32 scalars
  and one host fetch per round; disabled path bit-identical);
* :mod:`repro.telemetry.tracer` — host-side span tracing (``Tracer``,
  with ``block_until_ready`` boundaries) plus the ``Counters`` registry
  and bounded ``Histogram``;
* :mod:`repro.telemetry.export` / :mod:`~repro.telemetry.schema` /
  :mod:`~repro.telemetry.latency` — the JSONL sink, the validated event
  schema, and serving latency percentiles.

``Telemetry`` (:mod:`repro.telemetry.core`) composes them; every engine
takes ``telemetry=`` and defaults to ``Telemetry.disabled()``.
"""
from repro.telemetry.core import Telemetry
from repro.telemetry.drift import (delta_dispersion, ef_residual_norm,
                                   momentum_alignment, round_metrics,
                                   streaming_dispersion, streaming_sq_norm,
                                   update_norm)
from repro.telemetry.export import JsonlSink
from repro.telemetry.latency import latency_summary, request_itl
from repro.telemetry.schema import EVENT_SCHEMA, validate_event, validate_jsonl
from repro.telemetry.tracer import Counters, Histogram, Span, Tracer

__all__ = [
    "Telemetry",
    "Tracer", "Span", "Counters", "Histogram",
    "JsonlSink",
    "latency_summary", "request_itl",
    "EVENT_SCHEMA", "validate_event", "validate_jsonl",
    "round_metrics", "delta_dispersion", "momentum_alignment",
    "ef_residual_norm", "update_norm",
    "streaming_sq_norm", "streaming_dispersion",
]
