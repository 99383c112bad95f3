"""Reduce a profiler trace (``.xplane.pb``) to device intervals and spans.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU
plane (``/device:TPU:<n>``) holds a line of XLA programs ("XLA Modules")
and a line of the operations inside them ("XLA Ops"); host planes hold
the benchmark's ``TraceAnnotation`` spans (``bench.*``), whose arguments
(prompt tokens, active slots, attended context) come back as the event's
stats. All event times are in nanoseconds on the trace's one clock.

``Trace`` keeps what the metric readers need: per device, the programs
and operations with their intervals; the benchmark's host spans; the
traced window; and the union of busy intervals, from which the idle
gaps follow.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
MODULES, OPS = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: float          # ns
    end: float            # ns
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


def program_name(name: str) -> str:
    """``jit__decode(123)`` -> ``jit__decode``."""
    return name.split("(")[0].strip()


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


@dataclass
class Trace:
    devices: dict            # device id -> {"modules": [...], "ops": [...]}
    spans: list              # host bench.* spans, by start
    window: tuple            # (start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, dev: int) -> list[tuple[float, float]]:
        d = self.devices.get(dev, {"ops": [], "modules": []})
        evs = d["ops"] or d["modules"]
        lo, hi = self.window
        return union((max(e.start, lo), min(e.end, hi)) for e in evs
                     if e.end > lo and e.start < hi)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([sum(e - s for s, e in self.busy(d))
                              for d in self.devices])) * 1e-9

    def idle_gaps(self, dev: int) -> list[tuple[float, float]]:
        b = self.busy(dev)
        lo, hi = self.window
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def modules(self, dev: int, part: str) -> list[Event]:
        """Programs of ``dev`` whose name holds ``part`` (``_decode``)."""
        return [e for e in self.devices.get(dev, {}).get("modules", [])
                if part in program_name(e.name)]

    def spans_named(self, name: str) -> list[Event]:
        return [s for s in self.spans if s.name == name]

    def label(self, t: float) -> str:
        """The innermost benchmark span around host time ``t``."""
        inner = None
        for s in self.spans:
            if s.start <= t < s.end and (inner is None or s.dur < inner.dur):
                inner = s
        return inner.name if inner else "engine loop"


def loads(xspace: bytes) -> Trace:
    """The ``Trace`` of a serialized XSpace."""
    import jax
    return reduce(jax.profiler.ProfileData.from_serialized_xspace(xspace))


def reduce(pd) -> Trace:
    """The ``Trace`` of a ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        if m:
            d = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULES: "modules", OPS: "ops"}.get(line.name)
                if key:
                    d[key] = [Event(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, dict(e.stats))
                              for e in line.events]
            devices[int(m.group(1))] = d
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Event(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats)))
    spans.sort(key=lambda s: s.start)
    times = [x for d in devices.values() for k in ("modules", "ops")
             for e in d[k] for x in (e.start, e.end)]
    times += [x for s in spans for x in (s.start, s.end)]
    window = (min(times), max(times)) if times else (0.0, 0.0)
    return Trace(devices=devices, spans=spans, window=window)


def paired(spans: list[Event], programs: list[Event]):
    """Pair each host call span with the device program it launched: the
    first unpaired program that starts after the span starts. Calls whose
    program fell outside the trace, and programs launched before it
    began, are dropped."""
    out, j = [], 0
    programs = sorted(programs, key=lambda e: e.start)
    for s in spans:
        while j < len(programs) and programs[j].start < s.start:
            j += 1
        if j == len(programs):
            break
        out.append((s, programs[j]))
        j += 1
    return out


def op_name(ev: Event, modules: list[Event]) -> str:
    """``jit__decode/%while.4`` for an operation (its HLO text cut at
    ``=``) inside the program ``jit__decode(...)``."""
    name = ev.name.split(" = ")[0].strip()
    i = bisect.bisect_right([m.start for m in modules], ev.start) - 1
    if i >= 0 and ev.start < modules[i].end:
        return f"{program_name(modules[i].name)}/{name}"
    return name


def self_times(evs: list[Event], lo: float, hi: float):
    """``(event, ns)`` for each event: its time inside ``[lo, hi)`` less
    that of the events nested in it (a loop's body operations lie inside
    the loop's own event on the same line)."""
    out, stack = [], []
    for e in sorted(evs, key=lambda e: (e.start, -e.end)):
        own = max(0.0, min(e.end, hi) - max(e.start, lo))
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        if stack:
            stack[-1][1][0] -= own
        cell = [own]
        stack.append((e, cell))
        out.append((e, cell))
    return [(e, c[0]) for e, c in out]


def breakdown(tr: Trace, dev: int = 0, top: int = 10) -> dict:
    """The device operations that took most time (self time, summed by
    program and operation), and the longest idle gaps labelled by the
    benchmark span the host was in."""
    tot = {}
    lo, hi = tr.window
    d = tr.devices.get(dev, {"ops": [], "modules": []})
    mods = sorted(d["modules"], key=lambda m: m.start)
    for e, ns in self_times(d["ops"] or d["modules"], lo, hi):
        if ns > 0:
            key = op_name(e, mods)
            tot[key] = tot.get(key, 0.0) + ns
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(tr.idle_gaps(dev), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": [[tr.label((s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps]}
