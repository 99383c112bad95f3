"""itl_p99_ms: 99th percentile of the gaps between consecutive tokens of
every request, over all requests. A request's tokens come from the
decode steps of consecutive engine iterations; the gap between two is
the time between those iterations' loop tops (``step_log``), so it holds
whatever else ran in between: prefills of newly admitted requests and
the host's booking. Each gap is shifted by one iteration, its size is
not."""
import numpy as np


def gaps(step_log):
    """Every inter-token gap, in seconds, from a list of step events."""
    last, out = {}, []
    for ev in step_log:
        for rid in ev.decoded:
            if rid in last:
                out.append(ev.now - last[rid])
            last[rid] = ev.now
    return out


def read(run):
    g = gaps(run.step_log)
    if run.traffic["kind"] != "open" or not g:
        return None
    return float(np.percentile(g, 99)) * 1e3
