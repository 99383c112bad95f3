"""itl_clean_p99_ms: 99th percentile of the inter-token gaps that hold no
admission, over all requests, in milliseconds.

A gap is the time between two consecutive tokens of one request reaching
the host (``ServeRequest.token_t``, stamped by the engine). It holds an
admission when an engine iteration that admitted a request began inside
it (a ``step_log`` event with ``admitted`` whose ``now`` lies in the
gap): the batch then waited for that prefill. Without those gaps the
tail is the decode step plus the host's round trip, off the prefill
cliff that ``itl_p99_ms`` sits on. A program that stamps no ``token_t``
gives nothing to read."""
import bisect

import numpy as np


def gaps(requests, step_log):
    """(clean, stalled): every inter-token gap in seconds, split by
    whether an admitting iteration began inside it."""
    admits = sorted(ev.now for ev in step_log if ev.admitted)
    clean, stalled = [], []
    for r in requests:
        t = getattr(r, "token_t", None) or []
        for a, b in zip(t, t[1:]):
            i = bisect.bisect_right(admits, a)
            held = i < len(admits) and admits[i] < b
            (stalled if held else clean).append(b - a)
    return clean, stalled


def read(run):
    clean, _ = gaps(run.requests, run.step_log)
    if run.traffic["kind"] != "open" or not clean:
        return None
    return float(np.percentile(clean, 99)) * 1e3
