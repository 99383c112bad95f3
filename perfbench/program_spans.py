"""The serving engine's own spans, read beside the device's idle gaps.

    python3 perfbench/program_spans.py <dir>/trace.xplane.pb[.gz]

reads a profile that ``run.py --trace 1 --trace-dir <dir>`` kept and
prints one JSON object. The engine (``repro.serve.continuous``) wraps
each boundary of its loop in a ``serve.*`` ``TraceAnnotation``: ingest,
admit (holding prefill, first_token and insert), decode, sample, book
and idle. They land on the profiler's clock beside the device's
programs, so each idle gap of the device falls inside the span of what
the host was doing. ``xplane.reduce`` keeps only the benchmark's own
``bench.*`` spans; this module reads the ``serve.*`` ones (``spans``)
and gives:

- ``gap_split``: for each pair of consecutive decode programs, the
  device-idle time between them (the gap ``host_gap_ms`` takes the
  median of) split by the innermost ``serve.*`` span around it: under
  ``serve.sample`` or ``serve.first_token`` the host waits for tokens
  (``sync``); under any other it books, ingests, admits or dispatches
  (``host``); under none, ``none``;
- ``labelled_gaps``: the longest idle gaps of the window, each named by
  the innermost ``bench.*`` or ``serve.*`` span most of it fell under;
- ``clock_offset``: how far the device's times lag the host's.
  On one TPU v5e the device's decode programs began about a millisecond
  before the host's call that launched them returned, some before that
  call began: the profile aligns the two clocks only to about a
  millisecond. The gaps are put on the host's clock by the median of
  (end of a ``serve.decode`` span - start of the decode program nearest
  it) where that is positive, before they are split or named;
- ``admit_stall_ms_per_ktok``: the time the ``serve.admit`` spans took
  per 1000 prompt tokens they carry: how long the decode batch waited
  for each admission.
"""
from __future__ import annotations

import bisect
import gzip
import json
import os
import sys

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from perfbench import xplane  # noqa: E402

PREFIX = "serve."
SYNC = ("serve.sample", "serve.first_token")


def spans(pd) -> list[xplane.Event]:
    """The ``serve.*`` host events of a ``jax.profiler.ProfileData``, by
    start."""
    out = [xplane.Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
           for plane in pd.planes if not xplane.DEVICE.match(plane.name)
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: s.start)


def load(path: str):
    """(Trace, serve spans) of a ``.xplane.pb`` file, or of one gzipped
    (``.xplane.pb.gz``)."""
    import jax
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as fh:
        pd = jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    return xplane.reduce(pd), spans(pd)


def innermost(evs, t: float):
    """The shortest event of ``evs`` around time ``t``, or None."""
    inner = None
    for s in evs:
        if s.start <= t < s.end and (inner is None or s.dur < inner.dur):
            inner = s
    return inner


def by_span(idle, evs) -> dict:
    """Nanoseconds of the intervals ``idle`` under each innermost span
    name of ``evs`` (None: under no span), cut wherever a span starts or
    ends."""
    out = {}
    for lo, hi in idle:
        near = [s for s in evs if s.end > lo and s.start < hi]
        cuts = sorted({lo, hi} | {x for s in near for x in (s.start, s.end)
                                  if lo < x < hi})
        for a, b in zip(cuts, cuts[1:]):
            s = innermost(near, (a + b) / 2)
            name = s.name if s else None
            out[name] = out.get(name, 0.0) + b - a
    return out


def split(idle, evs) -> dict:
    """Nanoseconds of the intervals ``idle`` under a sync span, another
    span and none."""
    out = {"sync": 0.0, "host": 0.0, "none": 0.0}
    for name, ns in by_span(idle, evs).items():
        out["none" if name is None else
            "sync" if name in SYNC else "host"] += ns
    return out


def clock_offset(tr: xplane.Trace, evs, dev: int = 0) -> float:
    """Nanoseconds to add to ``dev``'s times to put them on the clock of
    the host spans ``evs``: the median of (end of each ``serve.decode``
    span - start of the decode program that starts nearest it), or 0
    where that is not positive or there is no pair."""
    starts = sorted(m.start for m in tr.modules(dev, "_decode"))
    d = []
    for s in evs:
        if s.name == "serve.decode" and starts:
            i = bisect.bisect_left(starts, s.end)
            near = min(starts[max(0, i - 1):i + 1],
                       key=lambda t: abs(s.end - t))
            d.append(s.end - near)
    return max(0.0, float(np.median(d))) if d else 0.0


def gap_split(tr: xplane.Trace, evs, dev: int = 0):
    """One ``split`` per pair of consecutive decode programs of ``dev``:
    of the device-idle time between the first's end and the second's
    start, on the host's clock (``clock_offset``)."""
    mods = sorted(tr.modules(dev, "_decode"), key=lambda m: m.start)
    gaps = tr.idle_gaps(dev)
    off = clock_offset(tr, evs, dev)
    out = []
    for a, b in zip(mods, mods[1:]):
        idle = [(max(s, a.end) + off, min(e, b.start) + off)
                for s, e in gaps if e > a.end and s < b.start]
        out.append(split(idle, evs))
    return out


def labelled_gaps(tr: xplane.Trace, evs, dev: int = 0, top: int = 10):
    """The ``top`` longest idle gaps of ``dev`` as ``[label, seconds]``,
    named by the innermost benchmark or engine span that most of the gap
    fell under on the host's clock ("engine loop": under none)."""
    both = list(tr.spans) + list(evs)
    gaps = sorted(tr.idle_gaps(dev), key=lambda g: g[0] - g[1])[:top]
    off = clock_offset(tr, evs, dev)
    out = []
    for s, e in gaps:
        names = by_span([(s + off, e + off)], both)
        name = max(names, key=names.get)
        out.append([name or "engine loop", (e - s) * 1e-9])
    return out


def admit_stall_ms_per_ktok(evs):
    """Milliseconds of ``serve.admit`` spans per 1000 prompt tokens they
    carry, or None without one."""
    adm = [s for s in evs if s.name == "serve.admit"]
    tokens = sum(int(s.stats.get("prompt_tokens", 0)) for s in adm)
    if not tokens:
        return None
    return sum(s.dur for s in adm) * 1e-6 / (tokens / 1000.0)


def summary(tr: xplane.Trace, evs, dev: int = 0) -> dict:
    """The medians of the gap split, in milliseconds, beside the median
    whole gap, and the other readings above."""
    parts = gap_split(tr, evs, dev)
    med = {k: float(np.median([p[k] for p in parts])) * 1e-6
           if parts else None for k in ("sync", "host", "none")}
    whole = [sum(p.values()) for p in parts]
    return {"pairs": len(parts),
            "clock_offset_ms": clock_offset(tr, evs, dev) * 1e-6,
            "gap_sync_ms": med["sync"], "gap_host_ms": med["host"],
            "gap_none_ms": med["none"],
            "host_gap_ms": float(np.median(whole)) * 1e-6 if whole else None,
            "admit_stall_ms_per_ktok": admit_stall_ms_per_ktok(evs),
            "span_counts": {n: sum(s.name == n for s in evs)
                            for n in sorted({s.name for s in evs})},
            "idle_gaps": labelled_gaps(tr, evs, dev)}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    tr, evs = load(args[0])
    print(json.dumps(summary(tr, evs)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
