"""Unified tracing + metrics layer (DESIGN.md section 16).

The load-bearing guarantees pinned here:

  * **Non-interference**: a traced engine run on the stateful virtual
    clock is bit-identical to an untraced one — the tracer never calls
    the clock on an engine path (proven with a tracer whose own clock
    *raises*), so instrumentation cannot perturb admission order.
  * **Span-tree stability**: two same-seed traced runs export
    byte-identical Chrome-trace JSON (track registration order fixes
    tid assignment).
  * The exported trace is structurally valid (``obs.validate``), and the
    validator actually rejects malformed traces (unmatched ends,
    non-monotone timestamps, missing categories).
  * SLO scheduling decisions land on the trace with their *reasons*
    (shed instants carry the reason, preempt instants the projected
    TTFT that justified the eviction).
  * ``BoundedLog`` keeps list semantics while capping memory; the
    engine's ``log_cap`` threads it through and counts evictions.
  * Every Runner Record carries the uniform environment stamp, and
    ``diff`` refuses (exit 2) to gate thresholds across environments.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import all_archs, smoke
from repro.models import registry as model_registry
from repro.obs import (BoundedLog, MetricsRegistry, NULL, Tracer, current,
                       span_times, use, validate_chrome_trace)
from repro.obs import trace as obs_trace


@pytest.fixture(scope="module")
def cfg_params():
    c = smoke(all_archs()["olmo-1b"])
    return c, model_registry.init_params(c, jax.random.key(0))


def _vclock():
    tick = {"t": 0.0}

    def clock():
        tick["t"] += 1.0
        return tick["t"]
    return clock


def _raising_clock():
    def clock():
        raise RuntimeError("tracer clock called on an engine path")
    return clock


def _reqs(c, n=3, max_new=4, salt=0):
    from repro.serve.scheduler import ServeRequest
    base = np.arange(8, dtype=np.int32) % c.vocab_size
    return [ServeRequest(prompt=(base + salt + i) % c.vocab_size,
                         max_new_tokens=max_new, arrival_s=float(i))
            for i in range(n)]


# ---------------------------------------------------------------------------
# tracer basics + export
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_export_validates():
    tr = Tracer(metadata={"who": "test"})
    tr.begin("engine", "admit", "engine", t=1.0, rid=0)
    tr.begin("engine", "prefill", "engine", t=1.5)
    tr.end("engine", t=2.0)
    tr.instant("scheduler", "shed", "scheduler", t=2.5, reason="memory")
    tr.counter("kv", "kv_pages", t=2.5, free=3, used=5)
    tr.end("engine", t=3.0, tokens=1)
    data = tr.chrome_trace()
    assert validate_chrome_trace(data) == []
    assert data["otherData"] == {"who": "test"}
    # per-track metadata rows name the tracks for Perfetto
    names = {e["args"]["name"] for e in data["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"engine", "scheduler", "kv"} <= names
    # timestamps are microseconds
    ts = [e["ts"] for e in data["traceEvents"] if e["ph"] == "B"]
    assert ts == [1e6, 1.5e6]
    # the nested pair closed innermost-first
    agg = span_times(tr.events, track="engine")
    assert agg["prefill"] == {"count": 1, "total_s": pytest.approx(0.5)}
    assert agg["admit"] == {"count": 1, "total_s": pytest.approx(2.0)}


def test_tracer_unmatched_end_raises():
    tr = Tracer()
    with pytest.raises(RuntimeError, match="no open span"):
        tr.end("engine", t=1.0)


def test_null_tracer_is_inert():
    assert NULL.enabled is False
    NULL.begin("x", "y")
    NULL.end("x")
    NULL.instant("x", "y")
    NULL.counter("x", "y", v=1)
    with NULL.span("x", "y"):
        pass
    NULL.metrics.count("n")
    assert NULL.metrics.snapshot() == {"counters": {}}
    assert NULL.events == ()


def test_current_use_restores_previous():
    assert current() is NULL
    tr = Tracer()
    with use(tr):
        assert current() is tr
        with use(None):
            assert current() is NULL
    assert current() is NULL


def test_metrics_registry_counts_gauges_histograms():
    # the registry keeps counters only: queue depth, slots and KV pages
    # are counter tracks of the trace, latencies the request stamps
    m = MetricsRegistry()
    m.count("admits")
    m.count("admits", 2)
    m.count("sheds", 0.5)
    snap = m.snapshot()
    assert snap == {"counters": {"admits": 3.0, "sheds": 0.5}}
    # a snapshot is a copy: later counts do not reach it
    m.count("admits")
    assert snap["counters"]["admits"] == 3.0
    assert m.snapshot()["counters"]["admits"] == 4.0


# ---------------------------------------------------------------------------
# validator negatives (the CI smoke's teeth)
# ---------------------------------------------------------------------------

def _wrap(events):
    return {"traceEvents": events}


def test_validator_rejects_unmatched_end():
    bad = _wrap([{"ph": "E", "pid": 1, "tid": 0, "name": "x",
                  "cat": "c", "ts": 1.0, "args": {}}])
    assert any("unmatched" in p.lower() or "no open" in p.lower()
               for p in validate_chrome_trace(bad))


def test_validator_rejects_nonmonotone_timestamps():
    bad = _wrap([
        {"ph": "i", "pid": 1, "tid": 0, "name": "a", "cat": "c",
         "ts": 5.0, "args": {}},
        {"ph": "i", "pid": 1, "tid": 0, "name": "b", "cat": "c",
         "ts": 4.0, "args": {}}])
    assert any("monoton" in p.lower() for p in validate_chrome_trace(bad))


def test_validator_rejects_missing_required_category():
    ok = _wrap([{"ph": "i", "pid": 1, "tid": 0, "name": "a", "cat": "c",
                 "ts": 1.0, "args": {}}])
    assert validate_chrome_trace(ok) == []
    probs = validate_chrome_trace(ok, require_categories=("engine",))
    assert any("engine" in p for p in probs)


def test_validator_rejects_unclosed_span():
    bad = _wrap([{"ph": "B", "pid": 1, "tid": 0, "name": "x", "cat": "c",
                  "ts": 1.0, "args": {}}])
    assert any("unclosed" in p.lower() or "open" in p.lower()
               for p in validate_chrome_trace(bad))


# ---------------------------------------------------------------------------
# non-interference: the hard contract
# ---------------------------------------------------------------------------

def test_traced_run_identical_to_untraced_on_virtual_clock(cfg_params):
    """Same seed, same virtual clock; the traced run's tracer has a
    clock that RAISES — any tracer-initiated clock call on an engine
    path dies loudly instead of silently advancing virtual time."""
    from repro.serve.continuous import ContinuousEngine
    c, params = cfg_params

    plain_reqs = _reqs(c)
    plain = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                             block_size=4, clock=_vclock())
    plain.run(plain_reqs)

    tr = Tracer(clock=_raising_clock())
    traced_reqs = _reqs(c)
    traced = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                              block_size=4, clock=_vclock(), tracer=tr)
    traced.run(traced_reqs)

    assert [r.generated for r in traced_reqs] \
        == [r.generated for r in plain_reqs]
    assert [(r.t_admit, r.t_first_token, r.t_done) for r in traced_reqs] \
        == [(r.t_admit, r.t_first_token, r.t_done) for r in plain_reqs]
    assert list(traced.step_log) == list(plain.step_log)
    assert list(traced.scheduler.admit_log) == list(plain.scheduler.admit_log)
    # and the trace itself is real: spans per phase, one track per slot
    assert validate_chrome_trace(tr.chrome_trace()) == []
    agg = span_times(tr.events, track="engine")
    assert {"admit", "prefill", "decode"} <= set(agg)
    assert {"slot0", "slot1"} <= {e["track"] for e in tr.events}


def test_span_tree_stable_across_same_seed_runs(cfg_params):
    """Two identical traced runs export byte-identical Chrome JSON."""
    from repro.serve.continuous import ContinuousEngine
    c, params = cfg_params
    dumps = []
    for _ in range(2):
        tr = Tracer(clock=_raising_clock())
        eng = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                               block_size=4, clock=_vclock(), tracer=tr)
        eng.run(_reqs(c))
        dumps.append(json.dumps(tr.chrome_trace(), sort_keys=True))
    assert dumps[0] == dumps[1]


def test_trace_timestamps_monotone_across_two_runs(cfg_params):
    """One tracer, two engine runs on one monotone clock: each run
    re-anchors its epoch at ``clock()`` so per-track timestamps stay
    monotone across runs (run-relative stamps would collide at 0)."""
    from repro.serve.continuous import ContinuousEngine
    c, params = cfg_params
    tr = Tracer(clock=_raising_clock())
    clock = _vclock()
    for salt in (0, 100):
        eng = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                               block_size=4, clock=clock, tracer=tr)
        eng.run(_reqs(c, salt=salt))
    assert validate_chrome_trace(tr.chrome_trace()) == []


# ---------------------------------------------------------------------------
# scheduling decisions on the record: shed + preempt instants
# ---------------------------------------------------------------------------

def test_shed_instants_carry_reason(cfg_params):
    from repro.serve.continuous import ContinuousEngine
    c, params = cfg_params
    tr = Tracer(clock=_raising_clock())
    reqs = _reqs(c, n=4, max_new=8)
    eng = ContinuousEngine(c, params, n_slots=1, cache_len=32,
                           block_size=4, clock=_vclock(), tracer=tr)
    eng.run(reqs, deadline_s=30.0)   # too tight for 4 requests on 1 slot
    shed = [e for e in tr.events
            if e["ph"] == "i" and e["name"] == "shed"]
    assert shed and all(e["args"]["reason"] == "deadline" for e in shed)
    assert len(shed) == len(eng.scheduler.shed_log)
    assert tr.metrics.snapshot()["counters"]["sheds"] == len(shed)


def test_preempt_instants_carry_projected_ttft(cfg_params):
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.scheduler import ClassSLO, ServeRequest, SLOPolicy
    c, params = cfg_params
    base = np.arange(8, dtype=np.int32) % c.vocab_size
    reqs = [ServeRequest(prompt=(base + i) % c.vocab_size,
                         max_new_tokens=12, arrival_s=0.0,
                         priority="batch") for i in range(4)]
    reqs += [ServeRequest(prompt=(base + 10 + i) % c.vocab_size,
                          max_new_tokens=4, arrival_s=3.0 + i,
                          priority="interactive") for i in range(2)]
    policy = SLOPolicy(classes={
        "interactive": ClassSLO(rank=0, ttft_s=6.0, tpot_s=50.0),
        "batch": ClassSLO(rank=1, ttft_s=500.0, tpot_s=500.0,
                          shed_after_s=200.0),
    }, default_class="batch")
    tr = Tracer(clock=_raising_clock())
    eng = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                           block_size=4, clock=_vclock(), slo=policy,
                           tracer=tr)
    eng.run(reqs)
    pre = [e for e in tr.events
           if e["ph"] == "i" and e["name"] == "preempt"]
    assert pre and len(pre) == len(eng.scheduler.preempt_log)
    for e in pre:
        assert e["args"]["victim_priority"] == "batch"
        assert e["args"]["projected_ttft_s"] is not None
    admits = [e for e in tr.events
              if e["ph"] == "i" and e["name"] == "admit"]
    assert {e["args"]["rid"] for e in admits} >= {r.rid for r in reqs}


# ---------------------------------------------------------------------------
# BoundedLog + engine log caps
# ---------------------------------------------------------------------------

def test_bounded_log_semantics():
    log = BoundedLog(cap=3)
    for i in range(5):
        log.append(i)
    assert log == [2, 3, 4]          # list equality holds
    assert log.dropped == 2
    assert BoundedLog() == [] and BoundedLog().dropped == 0
    unbounded = BoundedLog()
    for i in range(10):
        unbounded.append(i)
    assert list(unbounded) == list(range(10)) and unbounded.dropped == 0
    with pytest.raises(ValueError):
        BoundedLog(cap=0)


def test_engine_log_cap_bounds_step_log(cfg_params):
    from repro.serve.continuous import ContinuousEngine
    c, params = cfg_params
    reqs = _reqs(c, n=3, max_new=6)
    eng = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                           block_size=4, clock=_vclock(), log_cap=2)
    eng.run(reqs)
    assert len(eng.step_log) == 2 and eng.step_log.dropped > 0
    assert len(eng.scheduler.admit_log) <= 2
    # the kept suffix is the *latest* entries
    assert eng.step_log[-1].now >= eng.step_log[0].now
    assert all(r.done for r in reqs)   # capping logs never drops work


# ---------------------------------------------------------------------------
# overlap spans via the thread-local tracer
# ---------------------------------------------------------------------------

def test_overlap_schedule_emits_stage_spans():
    import jax.numpy as jnp
    from repro.parallel.overlap import run_schedule
    a = jnp.ones((8, 8), jnp.float32)
    tr = Tracer()
    with use(tr):
        run_schedule(2, lambda i: a * (i + 1), lambda buf: jnp.tanh(buf),
                     True)
    names = {e["name"] for e in tr.events if e["track"] == "overlap"}
    assert {"pack0", "pack1", "chain0", "chain1"} <= names
    assert all(e["args"].get("schedule") == "pipelined"
               for e in tr.events
               if e["track"] == "overlap" and e["ph"] == "B")
    snap = tr.metrics.snapshot()["counters"]
    assert snap["chains_issued"] == 2 and snap["chains_retired"] == 2
    assert validate_chrome_trace(
        tr.chrome_trace(), require_categories=("overlap",)) == []


# ---------------------------------------------------------------------------
# Runner env stamping + diff refusal
# ---------------------------------------------------------------------------

def test_runner_stamps_environment_on_every_record():
    from repro.experiments import registry as reg
    from repro.experiments.record import Record
    from repro.experiments.registry import experiment
    from repro.experiments.runner import Runner
    name = "zztest.obs_env"
    experiment(name, classes=("CPU",))(
        lambda *, duration: [Record(name, "x", "m", 1.0)])
    try:
        report = Runner(only=[name], records_dir=None).run()
    finally:
        reg.unregister(name)
    assert report.records
    for r in report.records:
        env = r.params["env"]
        assert set(env) == {"backend", "device_kind", "device_count",
                            "platform", "hostname"}
        assert env["device_count"] >= 1


def _env_stream(path, backend, value=1.0):
    from repro.experiments.record import Record
    env = {"backend": backend, "device_count": 1,
           "platform": "linux", "hostname": "h"}
    rows = [Record("e", "n", "tokens_per_sec", value,
                   params={"env": env})]
    path.write_text("\n".join(r.to_json() for r in rows) + "\n")
    return str(path)


def test_diff_refuses_cross_environment_gating(tmp_path, capsys):
    from repro.experiments.diff import main as diff_main
    old = _env_stream(tmp_path / "old.jsonl", "cpu")
    new = _env_stream(tmp_path / "new.jsonl", "tpu")
    rc = diff_main([old, new, "--threshold", "tokens_per_sec=-0.9"])
    assert rc == 2
    assert "ENV MISMATCH" in capsys.readouterr().err
    # --ignore-env overrides; identical values then gate clean
    assert diff_main([old, new, "--threshold", "tokens_per_sec=-0.9",
                      "--ignore-env"]) == 0
    # ungated diffs never refuse
    assert diff_main([old, new]) == 0
    # same-env streams gate without refusal
    old2 = _env_stream(tmp_path / "old2.jsonl", "cpu", value=10.0)
    new2 = _env_stream(tmp_path / "new2.jsonl", "cpu", value=0.5)
    assert diff_main([old2, new2,
                      "--threshold", "tokens_per_sec=-0.9"]) == 1


# ---------------------------------------------------------------------------
# serve.timeline + report rendering
# ---------------------------------------------------------------------------

def test_timeline_experiment_records_span_decomposition(tmp_path):
    from repro.core import serving
    out = tmp_path / "trace.json"
    recs = serving.timeline(duration=0.1, n_slots=2, cache_len=32,
                            block_size=4, prompt_lens=(4, 8), max_new=4,
                            max_requests=6, trace_out=str(out))
    by_metric = {}
    for r in recs:
        by_metric.setdefault(r.metric, []).append(r)
    tps = {r.name for r in by_metric["tokens_per_sec"]}
    assert {"load_0.5x", "load_1x"} <= tps
    spans = by_metric["span_time_s"]
    phases = {r.name.rpartition(".")[2] for r in spans}
    assert {"admit", "prefill", "decode"} <= phases
    for r in spans:
        assert r.params["span_count"] >= 1
        assert r.relative is None or 0.0 <= r.relative
    summary = by_metric["trace_events"][0]
    assert summary.params["counters"]["admits"] >= 6
    assert "engine" in summary.params["tracks"]
    data = json.loads(out.read_text())
    assert validate_chrome_trace(
        data, require_categories=("engine", "scheduler", "slot",
                                  "overlap")) == []


def test_timeline_table_renders_phase_fractions():
    from repro.analysis.report import timeline_table
    from repro.experiments.record import Record
    recs = [
        Record("serve.timeline", "load_0.5x", "tokens_per_sec", 100.0,
               relative=0.5,
               params={"offered_mult": 0.5, "requested_rps": 2.0}),
        Record("serve.timeline", "load_0.5x.decode", "span_time_s", 0.8,
               relative=0.8, params={"offered_mult": 0.5}),
        Record("serve.timeline", "load_0.5x.idle", "span_time_s", 0.1,
               relative=0.1, params={"offered_mult": 0.5}),
        Record("serve.timeline", "trace_summary", "trace_events", 42.0,
               params={"tracks": ["engine", "kv"],
                       "kv_watermark": {"peak_used": 3,
                                        "peak_frac": 0.5}}),
        # a foreign row must not leak into the table
        Record("serve.load_sweep", "load_0.5x", "tokens_per_sec", 1.0),
    ]
    table = timeline_table(recs)
    assert "decode %" in table and "idle %" in table
    row = next(line for line in table.splitlines()
               if line.startswith("| load_0.5x "))
    assert "| 100 |" in row and "80%" in row and "10%" in row \
        and "| 2.0 " in row
    assert "42" in table and "kv peak 3 slots (50% of pool)" in table
    assert table.count("load_0.5x") == 1   # one row, nothing duplicated


def test_runtime_knob_resolves_fresh_tracer():
    from repro import runtime
    from repro.obs import resolve
    assert resolve() is NULL
    with runtime.use_policy(obs_trace=True):
        tr = resolve()
        assert isinstance(tr, Tracer) and tr is not NULL
    tr2 = Tracer()
    with use(tr2):
        assert resolve() is tr2


# ---------------------------------------------------------------------------
# the profiler sink: serve.* spans, token stamps, program names
# ---------------------------------------------------------------------------

def _profiled(fn):
    """Run ``fn()`` under an in-memory profiler session; the ``serve.*``
    host events it recorded as (name, start_ns, end_ns, stats), by
    start."""
    from jax._src.lib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    session = _profiler.ProfilerSession(opts)
    try:
        fn()
    finally:
        xspace = session.stop()
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in pd.planes for line in plane.lines
           for e in line.events if e.name.startswith("serve.")]
    return sorted(evs, key=lambda e: (e[1], -e[2]))


def test_engine_spans_reach_the_profiler(cfg_params):
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.scheduler import ServeRequest
    c, params = cfg_params
    base = np.arange(8, dtype=np.int32) % c.vocab_size
    # the second request arrives long after the first is done, so the
    # loop idles in between (virtual clock: one second per call)
    reqs = [ServeRequest(prompt=base, max_new_tokens=3, arrival_s=0.0),
            ServeRequest(prompt=base[:5], max_new_tokens=3,
                         arrival_s=40.0)]
    eng = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                           block_size=4, clock=_vclock())
    assert eng.paged
    eng.run([ServeRequest(prompt=base, max_new_tokens=2)])    # compiles
    evs = _profiled(lambda: eng.run(reqs))
    names = {e[0] for e in evs}
    assert names == {"serve.ingest", "serve.admit", "serve.prefill",
                     "serve.first_token", "serve.insert", "serve.decode",
                     "serve.sample", "serve.book", "serve.idle"}
    ingest = [e[3] for e in evs if e[0] == "serve.ingest"]
    assert ingest == [{"n": 1}, {"n": 1}]
    admits = [e for e in evs if e[0] == "serve.admit"]
    assert [a[3] for a in admits] == [
        {"rid": r.rid, "slot": eng.scheduler.admit_log[i][1],
         "prompt_tokens": len(r.prompt)} for i, r in enumerate(reqs)]
    # prefill, first_token and insert lie inside their admission, in order
    for a in admits:
        inner = [e for e in evs if a[1] <= e[1] and e[2] <= a[2]
                 and e is not a]
        assert [e[0] for e in inner] == ["serve.prefill",
                                         "serve.first_token",
                                         "serve.insert"]
        assert all(e[3] == {} for e in inner)
    # each decoding iteration: decode, sample, book, sharing its step,
    # one after another; decode carries the active slots
    steps = {}
    for e in evs:
        if e[0] in ("serve.decode", "serve.sample", "serve.book"):
            steps.setdefault(e[3]["step"], []).append(e)
    decoding = [i for i, ev in enumerate(eng.step_log) if ev.decoded]
    assert sorted(s for s, es in steps.items()
                  if es[0][0] == "serve.decode") == decoding
    for s in decoding:
        es = steps[s]
        assert [e[0] for e in es] == ["serve.decode", "serve.sample",
                                      "serve.book"]
        assert es[0][3] == {"step": s,
                            "active": len(eng.step_log[s].decoded)}
        assert es[0][2] <= es[1][1] and es[1][2] <= es[2][1]
    # every working iteration books once, whether it decoded or not
    assert sorted(steps) == list(range(len(eng.step_log)))
    assert all(r.done and len(r.generated) == 3 for r in reqs)


def test_token_stamps_follow_generated_tokens(cfg_params):
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.scheduler import ClassSLO, ServeRequest, SLOPolicy
    c, params = cfg_params
    base = np.arange(8, dtype=np.int32) % c.vocab_size
    # the preemption scenario: batch requests evicted mid-stream restart
    # with their stamps cleared
    reqs = [ServeRequest(prompt=(base + i) % c.vocab_size,
                         max_new_tokens=12, arrival_s=0.0,
                         priority="batch") for i in range(4)]
    reqs += [ServeRequest(prompt=(base + 10 + i) % c.vocab_size,
                          max_new_tokens=4, arrival_s=3.0 + i,
                          priority="interactive") for i in range(2)]
    policy = SLOPolicy(classes={
        "interactive": ClassSLO(rank=0, ttft_s=6.0, tpot_s=50.0),
        "batch": ClassSLO(rank=1, ttft_s=500.0, tpot_s=500.0,
                          shed_after_s=200.0),
    }, default_class="batch")
    eng = ContinuousEngine(c, params, n_slots=2, cache_len=32,
                           block_size=4, clock=_vclock(), slo=policy)
    eng.run(reqs)
    assert eng.scheduler.preempt_log
    for r in reqs:
        assert r.done and len(r.token_t) == len(r.generated)
        assert r.token_t[0] == r.t_first_token
        assert all(a <= b for a, b in zip(r.token_t, r.token_t[1:]))
        # each later stamp ends the decode tick that decode_token_s times
        assert len(r.decode_token_s) == len(r.token_t) - 1
        assert r.token_t[-1] == r.t_done


SCRIPT_NAMES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import re
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs import all_archs, smoke
from repro.models import registry
from repro.parallel import compat
from repro.serve.step import make_cells

S, L, BS = 2, 32, 4
i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
mesh = compat.make_mesh((1, 2), ("data", "model"))

def module(fn, *args):
    return re.search(r"module @(\w+)", fn.lower(*args).as_text()).group(1)

# a config the page pool holds, and a windowed one it cannot
for arch, paged in (("olmo-1b", True), ("h2o-danube-3-4b", False)):
    cfg = smoke(all_archs()[arch])
    params = jax.eval_shape(
        lambda: registry.init_params(cfg, jax.random.key(0)))
    for m in (None, mesh):
        cells = make_cells(cfg, S, L, BS, S * L // BS + 1, mesh=m)
        assert cells.paged == paged, arch
        state = jax.eval_shape(cells.init_state)
        dec = (params, i32(S, 1), i32(S), state, i32(S, cells.max_pages))
        ins = jax.eval_shape(lambda: cells.insert_args(
            0, np.zeros(cells.max_pages, np.int32)))
        tokens = i32(1, 8)
        _, base = jax.eval_shape(cells.prefill, params, tokens)
        got = (module(cells.prefill, params, tokens),
               module(cells.decode, *dec),
               module(cells.insert, state, base, *ins))
        assert got == ("jit_serve_prefill", "jit_serve_decode",
                       "jit_serve_insert"), (arch, m is not None, got)
print("ok")
"""


def test_step_programs_have_stable_names():
    """Every build of the engine's cells (page pool and per-slot caches,
    one device and a 2-device mesh) lowers to modules ``jit_serve_prefill``,
    ``jit_serve_decode`` and ``jit_serve_insert``: the names a profile's
    readers key on."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT_NAMES], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")
