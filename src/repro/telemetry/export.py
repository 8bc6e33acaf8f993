"""Telemetry exporter: the JSONL event sink (DESIGN.md §Telemetry).

``JsonlSink`` writes one schema-validated JSON object per line — append-only,
flushed per event so a crashed run keeps everything emitted before the
crash.  Zero-dependency.
"""
from __future__ import annotations

import json

from repro.telemetry.schema import validate_event


class JsonlSink:
    """Append-only JSONL event sink.  Accepts a path (opened/owned) or any
    object with ``write`` (borrowed — not closed)."""

    def __init__(self, target):
        if hasattr(target, "write"):
            self._f, self._owns = target, False
        else:
            self._f, self._owns = open(target, "a"), True
        self.n_events = 0

    def emit(self, event: dict) -> None:
        validate_event(event)
        self._f.write(json.dumps(event, sort_keys=True) + "\n")
        self._f.flush()
        self.n_events += 1

    def close(self) -> None:
        if self._owns and not self._f.closed:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
