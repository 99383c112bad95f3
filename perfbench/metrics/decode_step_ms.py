"""decode_step_ms: mean device time of one decode-step program (the
``_decode`` XLA module) in the traced window, in milliseconds."""


def read(run):
    tr = run.trace
    mods = tr.modules(0, "_decode") if tr is not None else []
    if not mods:
        return None
    return sum(m.dur for m in mods) / len(mods) * 1e-6
