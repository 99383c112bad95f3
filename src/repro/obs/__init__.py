"""Unified observability: span tracing + metrics from scheduler to kernel.

``obs.trace`` is the span layer, with two sinks: the JSON ``Tracer`` on
the engine's clock (Chrome-trace-event export, Perfetto loadable) and
``annotate``, spans on the profiler's clock; ``obs.metrics`` the counter
registry riding on each tracer; ``obs.logbuf`` the ring-buffer cap for
the engine's otherwise-unbounded decision logs; ``obs.validate`` the schema validator
``scripts/check_trace.py`` and the tier-1 tests share.

The JSON sink is off by default behind a null object whose methods are
no-ops — the serve hot loop pays one attribute load and a falsy branch
when it is disabled; the profiler sink costs one inactive TraceMe per
span when no profiler session is open (DESIGN.md section 16).
"""
from repro.obs.logbuf import BoundedLog
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import (NULL, Tracer, annotate, current, resolve,
                             set_current, span_times, use)
from repro.obs.validate import validate_chrome_trace

__all__ = ["BoundedLog", "MetricsRegistry", "NULL", "Tracer", "annotate",
           "current", "resolve", "set_current", "span_times", "use",
           "validate_chrome_trace"]
