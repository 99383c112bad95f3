"""ttft_p90_ms: 90th percentile of time to first token over every request
that arrived in the window, from the time it was due (the engine stamps
its enqueue time at the due arrival, not when the loop noticed it)."""
import numpy as np


def read(run):
    ttft = [r.ttft_s for r in run.requests if r.ttft_s is not None]
    if run.traffic["kind"] != "open" or not ttft:
        return None
    return float(np.percentile(ttft, 90)) * 1e3
