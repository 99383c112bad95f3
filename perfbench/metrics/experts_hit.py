"""experts_hit: distinct experts the live tokens of a decode step hit,
averaged over the MoE layers and over the window's decode steps (the
engine's counter on ``step_log``; engines without it leave the metric
out)."""


def read(run):
    hits = [ev.experts_hit for ev in run.step_log
            if getattr(ev, "experts_hit", None) is not None]
    return sum(hits) / len(hits) if hits else None
