"""Counter registry attached to each tracer.

Counters accumulate (preemptions, sheds, admits, chains issued) and ride
out in the ``trace_summary`` Record through ``snapshot()``.  Queue depth,
slot occupancy and KV pages are counter *tracks* of the trace itself
(``Tracer.counter``), which Perfetto plots; per-request latencies are
the request stamps.

The registry is deliberately dumb — a plain dict, no locks, no export
thread: the serve engine is a single host loop.  The disabled path
(``_NullMetrics``) makes every update a no-op method call, matching the
tracer's null object.
"""
from __future__ import annotations


class MetricsRegistry:

    def __init__(self):
        self.counters: dict[str, float] = {}

    def count(self, name: str, delta: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + delta

    def snapshot(self) -> dict:
        """JSON-ready view of the counters."""
        return {"counters": dict(self.counters)}


class _NullMetrics:
    """No-op twin installed on the NULL tracer."""

    def count(self, *a, **k) -> None:
        pass

    def snapshot(self) -> dict:
        return {"counters": {}}
