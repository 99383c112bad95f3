"""slots_busy: mean number of requests each decode step advanced (the
active slots per step, from ``step_log``)."""


def read(run):
    n = [len(ev.decoded) for ev in run.step_log if ev.decoded]
    return sum(n) / len(n) if n else None
