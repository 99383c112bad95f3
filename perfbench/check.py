"""Decides ``correct``: served tokens against the configuration's reference.

After the window, a sample of the finished requests, drawn from the seed
and always holding the one with the most served tokens, is run through
the plain reference (``reference/<name>.py``) over its prompt and served
tokens. At every served position the number read is how far the served
token's logit lies below the reference's best (0 where they agree); the
widest such gap over the sample (``max_logit_gap``) and the mean over
every sampled served token (``mean_logit_gap``) are read, and each that
the configuration's ``check`` gives a limit is compared with it. Greedy
serving puts the program's own best first, so a gap is the program's
rounding deciding a near tie, and a wrong page, mask, position or head
shows as a gap of the logits' own scale.
Every served token is also checked to lie inside the vocabulary.
"""
from __future__ import annotations

import importlib

import numpy as np

# the gap readings a configuration's ``check`` may give a limit
GAPS = ("max_logit_gap", "mean_logit_gap")


def sample(requests, n: int, seed: int) -> list:
    """``n`` finished requests: the one with the most served tokens, and
    the rest drawn from the seed."""
    done = [r for r in requests if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated), len(r.prompt)))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    k = min(n - 1, len(rest))
    picks = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(picks)]


def reference(c: dict):
    return importlib.import_module(f"perfbench.reference.{c['reference']}")


def gap_readings(per_seq) -> dict:
    """The two numbers read from per-token gaps (one array per sampled
    request): the widest gap, and the mean over every served token."""
    allg = np.concatenate(per_seq)
    return {"max_logit_gap": float(allg.max()),
            "mean_logit_gap": float(allg.mean())}


def verdict(c: dict, key, requests, seed: int, lower=None) -> dict:
    """The numbers compared, each ``{"value", "limit"}``, and ``correct``.

    Every gap reading is returned (``readings``); those the
    configuration's ``check`` gives a limit are compared. With ``lower``
    the control's readings are taken too (``control``) and judged by the
    same limits (``control_correct``)."""
    picked = sample(requests, c["check"]["sample_requests"], seed)
    toks = [t for r in requests for t in r.generated]
    bad = int(sum(1 for t in toks if not 0 <= t < c["vocab_size"]))
    readings, control = dict.fromkeys(GAPS), None   # nothing finished
    if picked:
        out = reference(c).gaps(c, key, [(r.prompt, r.generated)
                                         for r in picked], lower=lower)
        readings = gap_readings([g for g, _ in out])
        if lower:
            control = gap_readings([gl for _, gl in out])

    def judged(values):
        checks = {"bad_tokens": {"value": bad, "limit": 0}}
        for name in GAPS:
            if name in c["check"]:
                checks[name] = {"value": values[name],
                                "limit": c["check"][name]}
        return checks

    checks = judged(readings)
    return {"correct": passes(checks), "checks": checks,
            "readings": readings, "control": control,
            # the control judged as a run: its gaps in the program's place
            "control_correct": passes(judged(control)) if control else None,
            "sampled": len(picked),
            "sampled_tokens": int(sum(len(r.generated) for r in picked))}


def passes(checks: dict) -> bool:
    """Every number compared is at or under its limit."""
    return bool(all(v["value"] is not None and v["value"] <= v["limit"]
                    for v in checks.values()))
