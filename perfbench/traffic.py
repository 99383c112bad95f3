"""One general generator for every traffic mix under ``traffic/``.

A mix is a JSON file of parameters; nothing about a mix lives in code:

- ``kind``: ``"open"`` (arrivals spread over the window at ``rate_rps``,
  served to completion, at most ``drain_s`` past the window) or
  ``"backlog"`` (``requests`` queued at t=0, more than the window can
  serve, so every slot stays busy until it closes);
- ``prompt_len`` / ``output_len``: ``{"median", "sigma", "buckets"}``, a
  lognormal snapped to the nearest bucket by log distance (the buckets
  bound how many prefill programs there are).

Every seed gets the same schedule: the sizes are the lognormal's
quantiles at ``(i + 0.5) / n`` and the gaps those of the exponential, put
in an order drawn once from a fixed stream (``SCHEDULE``), not from the
seed; the seed draws the token ids (and, elsewhere, the weights). A seed
that reordered the schedule would change the work a window holds: which
requests queue behind a burst, how many long prompts stall the batch,
how long the contexts grow. So two seeds run the same work, and the
spread between runs is the system's, not the generator's.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

# the stream that orders every mix's arrival gaps and sizes
SCHEDULE = 0


def snap(value: float, buckets) -> int:
    """Nearest bucket by log distance (buckets span octaves, so linear
    distance would favour the largest)."""
    logs = np.log(np.asarray(buckets, np.float64))
    return int(buckets[int(np.argmin(np.abs(logs - np.log(max(value,
                                                               1e-9)))))])


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal quantiles snapped to ``dist["buckets"]``, permuted."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = [snap(dist["median"] * np.exp(dist["sigma"] * zi),
                 dist["buckets"]) for zi in z]
    return rng.permutation(np.asarray(vals, np.int64))


def arrivals(mix: dict, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets of an open mix inside ``[0, seconds)``.

    The count is ``round(rate_rps * seconds)``. Unit exponential gaps at
    their quantiles, permuted and scaled to fill the window, place them:
    a Poisson process's shape with a count and gap multiset that do not
    depend on the seed."""
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def requests(mix: dict, vocab_size: int, seed: int, seconds: float):
    """The mix's requests for one run: a list of ``(arrival_s, prompt,
    max_new_tokens)``, prompt an int32 array, sorted by arrival. Only the
    prompts' token ids depend on ``seed``."""
    if mix["kind"] == "open":
        t = arrivals(mix, seconds, _rng(SCHEDULE, 0))
    elif mix["kind"] == "backlog":
        t = np.zeros(int(mix["requests"]))
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    n = len(t)
    plen = lengths(mix["prompt_len"], n, _rng(SCHEDULE, 1))
    outs = lengths(mix["output_len"], n, _rng(SCHEDULE, 2))
    tok = _rng(seed, 3)
    return [(float(t[i]),
             tok.integers(0, vocab_size, int(plen[i])).astype(np.int32),
             int(outs[i])) for i in range(n)]
