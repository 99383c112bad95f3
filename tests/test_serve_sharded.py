"""Sharded continuous serving: the differential multi-device tier, on 4
forced host devices (subprocess, like test_overlap).

The tensor-parallel engine (``ContinuousEngine(tp_size=N)`` routing its
cells through ``serve/step.make_cells``) may only change
*placement*: on the same seeded request set its emitted token streams
must be bit-identical to the single-device engine — for a burst and for
mixed arrivals on a virtual clock — its scheduling decisions
(``admit_log``) identical, and the compiled decode step must issue
exactly the expected per-kind collectives, for the page pool and for
per-slot caches (the silent-resharding guard:
a resharding XLA sneaks into the hot loop changes the counts before it
changes any latency number).  A ``ServeFabric`` straggler must compose
with the sharded engine: host-side stalls drag the whole TP step,
inflating TPOT without touching the tokens.

The differential runs use a float32 config: the engines are identical
modulo float rounding, and at bf16 a single TP all-reduce ulp (~0.03 at
logit scale ~3) can flip a near-tied greedy argmax — expected float
behavior, not a scheduling bug.  At f32 the reduction-order noise
(~1e-7) sits far below top-2 margins, so bit-identity is the honest
invariant.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax
import numpy as np
from repro.configs import all_archs, smoke
from repro.fabric import ServeFabric, canonical_conditions
from repro.models import registry
from repro.serve.continuous import ContinuousEngine
from repro.serve.loadgen import LoadSpec, make_requests
from repro.serve.scheduler import ServeRequest

cfg = dataclasses.replace(smoke(all_archs()["olmo-1b"]), dtype="float32")
params = registry.init_params(cfg, jax.random.key(0))
N_SLOTS, CACHE_LEN, BS, MAX_NEW = 4, 64, 8, 6

def build(tp, clock=None, fabric=None):
    kw = {"clock": clock} if clock is not None else {}
    return ContinuousEngine(cfg, params, n_slots=N_SLOTS,
                            cache_len=CACHE_LEN, block_size=BS,
                            tp_size=tp, fabric=fabric, **kw)

def toks(reqs):
    return [list(r.generated) for r in reqs]

# (a) burst identity: same seeded request set through tp=1/2/4 engines —
# token streams bit-identical, scheduling decisions identical, KV pool
# fully recycled; works at any device count the cache divides
engines, streams, logs = {}, {}, {}
for tp in (1, 2, 4):
    eng = build(tp)
    spec = LoadSpec(n_requests=6, rate_rps=0.0, prompt_lens=(8, 16),
                    max_new_tokens=MAX_NEW, vocab_size=cfg.vocab_size,
                    seed=3)
    reqs = eng.generate(make_requests(spec))
    engines[tp], streams[tp] = eng, toks(reqs)
    logs[tp] = list(eng.scheduler.admit_log)
    eng.scheduler.check()
    assert eng.kv.n_free == eng.kv.n_blocks, tp
    assert all(len(t) == MAX_NEW for t in streams[tp]), tp
assert streams[2] == streams[1], (streams[2], streams[1])
assert streams[4] == streams[1], (streams[4], streams[1])
assert logs[2] == logs[1] and logs[4] == logs[1], logs

# (b) mixed arrivals on a virtual clock: the continuous-batching
# observable (late request admitted mid-stream) survives sharding, and
# the streams stay identical to the single-device engine
def mixed(tp):
    tick = {"t": 0.0}
    def vclock():
        tick["t"] += 1.0
        return tick["t"]
    eng = build(tp, clock=vclock)
    a = ServeRequest(prompt=np.arange(8, dtype=np.int32),
                     max_new_tokens=12, arrival_s=0.0)
    b = ServeRequest(prompt=(np.arange(8, dtype=np.int32) + 5),
                     max_new_tokens=4, arrival_s=25.0)
    eng.run([a, b])
    assert a.t_first_token < b.t_admit < a.t_done, tp
    return toks([a, b])

assert mixed(4) == mixed(1)

# (c) the silent-resharding guard: per-kind trip-count-weighted
# collective counts of the compiled decode step match an explicit
# expectation, identically at tp=2 and tp=4 (the schedule is a function
# of the sharding rules, not the axis size), and the single-device build
# has no collectives at all — for the page pool (the olmo config) and
# for per-slot caches (the windowed h2o-danube config, which the pool
# cannot hold)
def guard(engines, expect):
    counts = {tp: e.cells.decode_collective_counts(e.params)
              for tp, e in engines.items()}
    assert counts[1] == {}, counts[1]
    assert counts[2] == expect, counts[2]
    assert counts[4] == expect, counts[4]

assert all(e.paged for e in engines.values())
guard(engines, {"all-reduce": 1.0, "all-gather": 1.0})
swa = dataclasses.replace(smoke(all_archs()["h2o-danube-3-4b"]),
                          dtype="float32")
swa_params = registry.init_params(swa, jax.random.key(0))
slot_engines = {tp: ContinuousEngine(swa, swa_params, n_slots=N_SLOTS,
                                     cache_len=CACHE_LEN, block_size=BS,
                                     tp_size=tp) for tp in (1, 2, 4)}
assert not any(e.paged for e in slot_engines.values())
guard(slot_engines, {"all-reduce": 1.0, "all-gather": 2.0})

# (d) ServeFabric straggler composes with the sharded engine: the stalls
# are host-side, so one slow device drags the whole tensor-parallel
# decode tick — TPOT inflates on the virtual clock, tokens do not move
def straggled(tp, cond):
    tick = {"t": 0.0}
    def vclock():
        tick["t"] += 1e-4
        return tick["t"]
    fab = None
    if cond is not None:
        fab = ServeFabric(cond, sleep=lambda s: tick.__setitem__(
            "t", tick["t"] + s))
    eng = build(tp, clock=vclock, fabric=fab)
    spec = LoadSpec(n_requests=6, rate_rps=0.0, prompt_lens=(8, 16),
                    max_new_tokens=MAX_NEW, vocab_size=cfg.vocab_size,
                    seed=3)
    reqs = eng.generate(make_requests(spec))
    return toks(reqs), [r.tpot_s for r in reqs], fab

clean_t, clean_tpot, _ = straggled(4, None)
deg_t, deg_tpot, fab = straggled(4, canonical_conditions()["straggler"])
assert deg_t == clean_t == streams[1]
assert fab.stalled_s["decode"] > 0.0 and fab.stalled_s["admit"] == 0.0
assert min(deg_tpot) > 10 * max(clean_tpot), (deg_tpot, clean_tpot)

print("ALL_OK")
"""


def test_sharded_engine_differential_4dev():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ALL_OK" in out.stdout, out.stdout + out.stderr


def test_tp_size_exceeding_devices_raises():
    """The engine refuses a tensor-parallel width the host cannot back,
    and names the XLA fabrication flag in the error."""
    import jax
    from repro.configs import all_archs, smoke
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    c = smoke(all_archs()["olmo-1b"])
    params = registry.init_params(c, jax.random.key(0))
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="device_count"):
        ContinuousEngine(c, params, tp_size=too_many)
