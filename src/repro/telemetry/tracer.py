"""Host-side span tracing + the unified counter registry (DESIGN.md
§Telemetry).

``Tracer`` times nested host-side phases with ``perf_counter`` around
explicit device-sync boundaries: a span is only meaningful where the host
actually waits for the device, so ``span(..., sync=tree)`` calls
``jax.block_until_ready`` on exit before the clock stops.  Spans attach at
the engines' real dispatch boundaries — ``round`` (one fused jit call in
the sync/pod engines), ``local_train`` / ``aggregate`` / ``transport.encode``
(the async engine's separate dispatch-group, flush, and broadcast calls),
``prefill_chunk`` / ``decode_step`` (the serving engine) — phases fused
inside one jit call cannot be separated without adding dispatches, and the
tracer never does.  An enabled span is also a ``jax.profiler.TraceAnnotation``
under the same nested name, so a profiler trace shows the engines' spans on
the device ops' clock (inside the round, the program's ``named_scope``
phases name the device ops themselves).

``Counters`` is the one registry every byte/count statistic lives behind:
``Transport`` accounts its four wire counters straight into it (the
engines' pre-telemetry ad-hoc ints are now views over the registry) and
the serving engine publishes queue/slot gauges the same way.

``Histogram`` is the bounded summary that replaced the async engine's
unbounded ``staleness_seen`` list: fixed integer bins plus an overflow
bucket, with exact count/mean/max tracked alongside — O(bins) memory no
matter how many observations arrive.

Everything here is host Python; the disabled tracer's ``span`` is a shared
no-op context manager, so telemetry-off engines pay one attribute lookup
per span site, touch no device state and write nothing to a trace.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Dict, Iterable, Optional

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Shared no-op span for the disabled tracer."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Span:
    """One timed host-side phase.  ``sync`` (any pytree of jax arrays) is
    blocked on before the clock stops, so the duration covers the device
    work the phase dispatched, not just the Python that launched it.  The
    span is a profiler ``TraceAnnotation`` of the same name over the same
    stretch, sync included."""

    __slots__ = ("tracer", "name", "sync", "t0", "annotation")

    def __init__(self, tracer: "Tracer", name: str, sync=None):
        self.tracer = tracer
        self.name = name
        self.sync = sync
        self.t0 = 0.0
        self.annotation = TraceAnnotation(name)

    def __enter__(self):
        self.tracer._stack.append(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.sync is not None:
            import jax
            jax.block_until_ready(self.sync)
        dur = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        self.tracer._stack.pop()
        self.tracer._record(self.name, dur)
        return False


class Tracer:
    """Nested span timing with bounded per-name duration reservoirs.

    Span names nest with ``/`` (a span opened inside another records as
    ``outer/inner``), and per-name statistics keep the most recent
    ``maxlen`` durations for percentiles plus exact count/total.
    """

    def __init__(self, enabled: bool = True, maxlen: int = 4096):
        self.enabled = enabled
        self.maxlen = maxlen
        self._stack: list = []
        self._durs: Dict[str, deque] = {}
        self._count: Dict[str, int] = {}
        self._total: Dict[str, float] = {}

    def span(self, name: str, sync=None):
        """Context manager timing one phase; no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        if self._stack:
            name = f"{self._stack[-1]}/{name}"
        return Span(self, name, sync)

    def _record(self, name: str, dur: float) -> None:
        if name not in self._durs:
            self._durs[name] = deque(maxlen=self.maxlen)
            self._count[name] = 0
            self._total[name] = 0.0
        self._durs[name].append(dur)
        self._count[name] += 1
        self._total[name] += dur

    def timings(self, name: str) -> list:
        """The retained durations (seconds) for one span name."""
        return list(self._durs.get(name, ()))

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span count/total and p50/p95 over the retained reservoir."""
        out = {}
        for name, durs in self._durs.items():
            s = sorted(durs)
            n = len(s)
            out[name] = {
                "count": self._count[name],
                "total_s": round(self._total[name], 6),
                "p50_s": round(s[n // 2], 6),
                "p95_s": round(s[min(n - 1, int(0.95 * n))], 6),
            }
        return out


class Counters:
    """Named monotonic counters and gauges — one snapshot-able registry.

    ``inc`` is the counter path (transport bytes, event counts); ``set``
    the gauge path (queue depth, slot occupancy).  Missing names read 0,
    so call sites never pre-register.
    """

    def __init__(self):
        self._c: Dict[str, float] = {}

    def inc(self, name: str, value: float = 1) -> None:
        self._c[name] = self._c.get(name, 0) + value

    def set(self, name: str, value: float) -> None:
        self._c[name] = value

    def get(self, name: str, default: float = 0):
        return self._c.get(name, default)

    def snapshot(self) -> Dict[str, float]:
        return dict(self._c)

    def __contains__(self, name: str) -> bool:
        return name in self._c


class Histogram:
    """Bounded integer histogram: bins ``0..n_bins-1`` plus an overflow
    bucket, with exact count / total / max tracked alongside so summary
    statistics stay exact even past the bound.  O(n_bins) memory for any
    number of observations — the replacement for keeping raw lists."""

    def __init__(self, n_bins: int = 32):
        if n_bins < 1:
            raise ValueError("Histogram needs at least one bin")
        self.n_bins = n_bins
        self.bins = [0] * n_bins
        self.overflow = 0
        self.count = 0
        self.total = 0
        self.max = 0

    def observe(self, value: int) -> None:
        v = int(value)
        if v < 0:
            raise ValueError(f"Histogram observes non-negative ints, got {v}")
        if v < self.n_bins:
            self.bins[v] += 1
        else:
            self.overflow += 1
        self.count += 1
        self.total += v
        if v > self.max:
            self.max = v

    def observe_many(self, values: Iterable[int]) -> None:
        for v in values:
            self.observe(v)

    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def reset(self) -> None:
        self.bins = [0] * self.n_bins
        self.overflow = 0
        self.count = 0
        self.total = 0
        self.max = 0

    def to_dict(self) -> Dict[str, object]:
        # trailing all-zero bins are trimmed so the export stays compact
        last = max((i for i, b in enumerate(self.bins) if b), default=-1)
        return {"bins": self.bins[:last + 1], "overflow": self.overflow,
                "count": self.count, "mean": round(self.mean(), 4),
                "max": self.max}
