"""host_gap_ms: median device-idle time between consecutive decode-step
programs in the traced window, in milliseconds: the time the device waits
on the host between one step and the next (minus any other program that
ran in between, such as a prefill)."""
import numpy as np


def read(run):
    tr = run.trace
    mods = sorted(tr.modules(0, "_decode"), key=lambda m: m.start) \
        if tr is not None else []
    if len(mods) < 2:
        return None
    busy = tr.busy(0)
    gaps = []
    for a, b in zip(mods, mods[1:]):
        lo, hi = a.end, b.start
        covered = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy)
        gaps.append(max(0.0, (hi - lo) - covered))
    return float(np.median(gaps)) * 1e-6
