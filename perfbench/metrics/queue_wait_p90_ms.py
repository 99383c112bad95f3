"""queue_wait_p90_ms: 90th percentile of the time a request waited from
its due arrival to its admission into a slot (the scheduler's stamps)."""
import numpy as np


def read(run):
    w = [r.queue_wait_s for r in run.requests if r.queue_wait_s is not None]
    return float(np.percentile(w, 90)) * 1e3 if w else None
