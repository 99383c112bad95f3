"""The unified Experiment API: registry round-trip, SKIP semantics,
Record JSON/CSV emission, the shared measurement harness, and the
planner consuming a Record stream end-to-end."""
import io

import pytest

from repro.core import planner
from repro.core.headroom import RooflineTerms
from repro.core.inpath import _wire_bytes
from repro.experiments import (Record, Runner, all_experiments, experiment,
                               measure, read_csv, read_jsonl, select,
                               write_csv, write_jsonl)
from repro.experiments import registry as reg
from repro.experiments.__main__ import main


# ---------------------------------------------------------------------------
# measurement harness
# ---------------------------------------------------------------------------

def test_measure_zero_duration_regression():
    """The seed's _timeit/_throughput loops hit UnboundLocalError when the
    deadline elapsed before the first iteration; the shared harness must
    always run at least one timed call."""
    calls = []
    m = measure(lambda: calls.append(1), duration=0.0)
    assert m.n >= 1
    assert len(calls) >= 2  # warmup + at least one timed call
    assert m.calls_per_sec > 0
    assert m.p10_s <= m.median_s <= m.p90_s


def test_measure_counts_calls():
    m = measure(lambda: None, duration=0.02, warmup=0)
    assert m.n > 1
    assert m.total_s >= 0.02


# ---------------------------------------------------------------------------
# Record schema + emitters
# ---------------------------------------------------------------------------

def _sample_records():
    return [
        Record("fam.exp", "row1", "ops_per_sec", 123.5, unit="ops/s",
               relative=1.5, params={"classes": ["CPU"], "size": 4096},
               wall_time=1e9, elapsed_s=0.1),
        Record("fam.exp", "row2", "skip", skipped=True, reason="no devices"),
        Record("fam.other", "row3", "error", error=True, reason="boom"),
    ]


def test_record_jsonl_roundtrip():
    recs = _sample_records()
    buf = io.StringIO()
    write_jsonl(recs, buf)
    buf.seek(0)
    back = list(read_jsonl(buf))
    assert back == recs


def test_record_csv_roundtrip():
    recs = _sample_records()
    buf = io.StringIO()
    write_csv(recs, buf)
    buf.seek(0)
    back = list(read_csv(buf))
    assert len(back) == len(recs)
    assert back[0].value == pytest.approx(123.5)
    assert back[0].params == {"classes": ["CPU"], "size": 4096}
    assert back[1].skipped and back[1].reason == "no devices"
    assert back[2].error


# ---------------------------------------------------------------------------
# registry + SKIP semantics
# ---------------------------------------------------------------------------

@pytest.fixture
def temp_experiment():
    names = []

    def make(name, fn=None, **kw):
        fn = fn or (lambda *, duration: [Record(name, "x", "m", 1.0)])
        experiment(name, **kw)(fn)
        names.append(name)
        return name

    yield make
    for n in names:
        reg.unregister(n)


def test_registry_roundtrip(temp_experiment):
    name = temp_experiment("zztest.alpha", classes=("CPU",), figure="Fig. 0")
    spec = reg.get(name)
    assert spec.name == name and spec.family == "zztest"
    assert spec.classes == ("CPU",)
    assert spec in all_experiments()
    assert [s.name for s in select(["zztest"])] == [name]
    assert [s.name for s in select([name])] == [name]
    with pytest.raises(ValueError):
        experiment(name)(lambda *, duration: [])


def test_runner_skips_on_unmet_device_requirement(temp_experiment):
    name = temp_experiment("zztest.needsmany", requires_devices=99)
    report = Runner(duration=0.0, only=[name], load_builtin=False,
                    records_dir=None).run()
    assert len(report.records) == 1
    r = report.records[0]
    assert r.skipped and not r.error and "99 devices" in r.reason
    assert report.ok  # SKIP is not an error


def test_runner_turns_exceptions_into_error_records(temp_experiment):
    def boom(*, duration):
        raise ValueError("broken rig")

    name = temp_experiment("zztest.boom", fn=boom)
    report = Runner(duration=0.0, only=[name], load_builtin=False,
                    records_dir=None).run()
    assert not report.ok
    assert report.errors[0].reason == "ValueError: broken rig"
    assert report.errors[0].experiment == name


def test_runner_emit_failures_propagate_not_recorded(temp_experiment):
    """A failing emit callback (closed pipe, full disk) must raise, not be
    misattributed to the experiment under measurement as an ERROR row."""
    name = temp_experiment("zztest.emitboom")

    def emit(r):
        raise BrokenPipeError("consumer went away")

    with pytest.raises(BrokenPipeError):
        Runner(duration=0.0, only=[name], load_builtin=False,
               records_dir=None).run(emit=emit)


def test_runner_stamps_wall_clock_metadata(temp_experiment):
    name = temp_experiment("zztest.stamp")
    report = Runner(duration=0.0, only=[name], load_builtin=False,
                    records_dir=None).run()
    r = report.records[0]
    assert r.wall_time is not None and r.elapsed_s is not None


def test_builtin_registrations_cover_all_families():
    reg.load_builtin()
    fams = {s.family for s in all_experiments()}
    assert {"headroom", "stressors", "classes", "inpath",
            "roofline", "serve"} <= fams
    assert reg.get("inpath.collectives").requires_devices == 2
    assert reg.get("inpath.bucketing").requires_devices == 2
    assert reg.get("inpath.headroom_overlap").requires_devices == 2
    # the serving family runs on a single device (the engine is local)
    assert reg.get("serve.load_sweep").requires_devices == 1
    assert reg.get("serve.continuous_vs_static").requires_devices == 1


def test_inpath_skips_on_single_device():
    report = Runner(duration=0.0, only=["inpath"], records_dir=None).run()
    import jax
    if len(jax.devices()) >= 2:
        pytest.skip("multi-device backend; inpath actually runs")
    assert report.records[0].skipped
    assert report.ok


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_jsonl_out_and_exit_code(tmp_path):
    out = tmp_path / "records.jsonl"
    rc = main(["--only", "headroom.transfer_nic", "--duration", "0.01",
               "--format", "jsonl", "--out", str(out), "--no-records"])
    assert rc == 0
    recs = list(read_jsonl(open(out)))
    assert len(recs) == 6  # 3 message sizes x 2 worker counts
    assert all(r.experiment == "headroom.transfer_nic" for r in recs)
    assert all(r.wall_time is not None for r in recs)


def test_cli_rejects_unknown_selection():
    assert main(["--only", "no.such.experiment"]) == 2


def test_cli_nonzero_on_error(tmp_path, temp_experiment):
    def boom(*, duration):
        raise RuntimeError("rig fell over")

    name = temp_experiment("zztest.clifail", fn=boom)
    out = tmp_path / "r.csv"
    rc = main(["--only", name, "--duration", "0.0", "--out", str(out),
               "--no-records"])
    assert rc == 1


# ---------------------------------------------------------------------------
# per-run Record persistence + diff
# ---------------------------------------------------------------------------

def test_runner_persists_jsonl_stream(tmp_path, temp_experiment):
    name = temp_experiment("zztest.persist")
    rdir = tmp_path / "records"
    report = Runner(duration=0.0, only=[name], load_builtin=False,
                    records_dir=str(rdir)).run()
    assert report.records_path is not None
    files = sorted(rdir.glob("run-*.jsonl"))
    assert [str(f) for f in files] == [report.records_path]
    back = list(read_jsonl(open(report.records_path)))
    assert back == report.records


def test_runner_persisted_streams_get_distinct_paths(tmp_path,
                                                     temp_experiment):
    name = temp_experiment("zztest.persist2")
    rdir = str(tmp_path / "records")
    mk = lambda: Runner(duration=0.0, only=[name], load_builtin=False,  # noqa: E731
                        records_dir=rdir)
    paths = {mk().run().records_path for _ in range(3)}
    assert len(paths) == 3  # same-second runs must not clobber each other


def test_diff_cli_reports_per_experiment_deltas(tmp_path, capsys):
    old = [Record("fam.a", "r1", "ops", 100.0),
           Record("fam.a", "r2", "ops", 5.0),
           Record("fam.b", "r3", "ops", 1.0)]
    new = [Record("fam.a", "r1", "ops", 150.0),          # changed
           Record("fam.a", "r2", "ops", 5.0),            # unchanged
           Record("fam.b", "r3", "ops", 1.0, skipped=True),  # flag flip
           Record("fam.c", "r4", "ops", 9.0)]            # added
    po, pn = tmp_path / "old.jsonl", tmp_path / "new.jsonl"
    write_jsonl(old, open(po, "w"))
    write_jsonl(new, open(pn, "w"))
    assert main(["diff", str(po), str(pn)]) == 0
    out = capsys.readouterr().out
    assert "fam.a:" in out and "r1.ops: 100 -> 150 (+50.0%)" in out
    assert "r2" not in out                    # unchanged rows stay silent
    assert "skipped False -> True" in out
    assert "r4.ops: added (9)" in out


def test_diff_cli_usage_error(tmp_path):
    assert main(["diff", "only-one.jsonl"]) == 2
    missing = tmp_path / "missing.jsonl"
    present = tmp_path / "present.jsonl"
    write_jsonl([], open(present, "w"))
    assert main(["diff", str(missing), str(present)]) == 2  # not a traceback


def test_diff_threshold_gates_per_metric(tmp_path, capsys):
    old = [Record("fam.a", "r1", "wall_s_per_call", 1.0),
           Record("fam.a", "r2", "wire_model", 100.0),
           Record("fam.a", "r3", "wall_s_per_call", None, skipped=True)]
    new = [Record("fam.a", "r1", "wall_s_per_call", 1.4),   # +40% (noise)
           Record("fam.a", "r2", "wire_model", 150.0),      # +50% (real)
           Record("fam.a", "r3", "wall_s_per_call", None, skipped=True)]
    po, pn = tmp_path / "old.jsonl", tmp_path / "new.jsonl"
    write_jsonl(old, open(po, "w"))
    write_jsonl(new, open(pn, "w"))
    # within the per-metric noise bound: report only, exit 0
    assert main(["diff", str(po), str(pn),
                 "--threshold", "wall_s_per_call=0.5"]) == 0
    # the tight-model metric violates its 0-tolerance bound: exit 1
    assert main(["diff", str(po), str(pn),
                 "--threshold", "wall_s_per_call=0.5",
                 "--threshold", "wire_model=0.0"]) == 1
    err = capsys.readouterr().err
    assert "THRESHOLD EXCEEDED" in err and "r2.wire_model" in err
    # skipped rows never violate; malformed spec is a usage error
    assert main(["diff", str(po), str(pn),
                 "--threshold", "nonsense"]) == 2


def test_diff_threshold_direction_gating(tmp_path, capsys):
    """'+' gates only increases, '-' only drops: a 2x rate improvement must
    not fail a drop-gated metric, and a wall-time improvement must not fail
    an increase-gated one."""
    old = [Record("fam.a", "rate", "ops_per_sec", 100.0),
           Record("fam.a", "wall", "wall_s_per_call", 2.0)]
    new = [Record("fam.a", "rate", "ops_per_sec", 250.0),   # 2.5x faster
           Record("fam.a", "wall", "wall_s_per_call", 0.5)]  # 4x faster
    po, pn = tmp_path / "old.jsonl", tmp_path / "new.jsonl"
    write_jsonl(old, open(po, "w"))
    write_jsonl(new, open(pn, "w"))
    assert main(["diff", str(po), str(pn),
                 "--threshold", "ops_per_sec=-0.9",
                 "--threshold", "wall_s_per_call=+1.0"]) == 0
    # the same magnitudes in the regression direction DO gate
    assert main(["diff", str(pn), str(po),
                 "--threshold", "ops_per_sec=-0.5",
                 "--threshold", "wall_s_per_call=+1.0"]) == 1
    err = capsys.readouterr().err
    assert "rate.ops_per_sec" in err and "wall.wall_s_per_call" in err


def test_diff_accepts_baseline_directory(tmp_path, capsys):
    """A directory of ``*.jsonl`` files is a valid diff argument — the
    curated-baseline layout: files concatenate in sorted order, later
    files winning repeated keys — and thresholds gate against it."""
    bdir = tmp_path / "baseline"
    bdir.mkdir()
    write_jsonl([Record("fam.a", "r1", "overlap_efficiency", 0.9),
                 Record("fam.a", "r2", "ops", 7.0)],
                open(bdir / "a.jsonl", "w"))
    write_jsonl([Record("fam.a", "r2", "ops", 8.0)],   # later file wins
                open(bdir / "b.jsonl", "w"))
    new = tmp_path / "new.jsonl"
    write_jsonl([Record("fam.a", "r1", "overlap_efficiency", 0.95),
                 Record("fam.a", "r2", "ops", 8.0)], open(new, "w"))
    assert main(["diff", str(bdir), str(new),
                 "--threshold", "overlap_efficiency=+1.0"]) == 0
    out = capsys.readouterr().out
    assert "r1.overlap_efficiency: 0.9 -> 0.95" in out
    assert "r2" not in out   # 8.0 == 8.0 after later-file override
    # a catastrophic schedule regression (ratio more than doubles) gates
    bad = tmp_path / "bad.jsonl"
    write_jsonl([Record("fam.a", "r1", "overlap_efficiency", 2.0)],
                open(bad, "w"))
    assert main(["diff", str(bdir), str(bad),
                 "--threshold", "overlap_efficiency=+1.0"]) == 1
    # an empty directory is a usage error, not a silent no-op diff
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["diff", str(empty), str(new)]) == 2


def test_repo_baseline_stream_parses_and_covers_overlap():
    """The shipped curated baseline must stay loadable and keep the
    acceptance-defining rows: overlap_efficiency per method with at least
    one *chunked* method strictly below 1.0 (the overlapped step beat the
    serial one on the reference 4-device mesh)."""
    import os
    bdir = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "experiments", "records", "baseline")
    from repro.experiments.diff import read_stream
    idx = read_stream(bdir)
    effs = {name: r.value for (exp, name, metric), r in idx.items()
            if metric == "overlap_efficiency"}
    assert {"stock", "int8_a2a", "int8_ring", "int8_pairwise",
            "ring"} <= set(effs)
    chunked = {"int8_a2a", "int8_ring", "ring"}
    assert any(effs[m] < 1.0 for m in chunked), effs
    for r in idx.values():   # curation stripped the volatile stamps
        assert "git_commit" not in r.params


def test_repo_baseline_serve_stream_covers_load_levels():
    """The curated serve baseline must keep the acceptance-defining rows:
    sustained throughput, p50/p99 TTFT/TPOT, and probe headroom at >= 3
    offered-load levels, plus both engine-comparison arms."""
    import os
    bdir = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "experiments", "records", "baseline")
    from repro.experiments.diff import read_stream
    idx = read_stream(bdir)
    levels = {name for (exp, name, metric) in idx
              if exp == "serve.load_sweep" and metric == "tokens_per_sec"
              and name.startswith("load_")}
    assert len(levels) >= 3, levels
    for metric in ("ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
                   "headroom_flops_per_s"):
        have = {name for (exp, name, m) in idx
                if exp == "serve.load_sweep" and m == metric}
        assert levels <= have, metric
    arms = {name for (exp, name, metric) in idx
            if exp == "serve.continuous_vs_static"}
    assert arms == {"static", "continuous"}


def test_runner_stamps_git_commit_in_params(temp_experiment):
    import subprocess
    sha = subprocess.run(["git", "rev-parse", "HEAD"], capture_output=True,
                         text=True).stdout.strip()
    if not sha:
        pytest.skip("not running inside a git repo")
    name = temp_experiment("zztest.commitstamp")
    report = Runner(duration=0.0, only=[name], load_builtin=False,
                    records_dir=None).run()
    assert report.records[0].params.get("git_commit") == sha


# ---------------------------------------------------------------------------
# wire-byte model (satellite: int8_a2a scale accounting)
# ---------------------------------------------------------------------------

def test_wire_bytes_int8_a2a_models_per_block_scales():
    n, size = 4, 1 << 20
    a2a = _wire_bytes(n, size, "int8_a2a")
    # int8 payload + one fp32 scale per chunk block, both exchange phases
    assert a2a == int(2 * (n - 1) / n * (size + n * 4))
    # the seed's formula collapsed the scale term to a constant 4 bytes;
    # the fixed model scales with payload size
    assert _wire_bytes(n, 2 * size, "int8_a2a") == pytest.approx(
        2 * a2a, rel=1e-3)
    # compression still wins vs fp32 wire
    assert a2a < _wire_bytes(n, size, "stock") / 3.9


def test_wire_bytes_int8_ring_models_compressed_all_gather():
    """``ring_allreduce(wire_int8=True)`` quantizes every reduce-scatter hop
    AND the accumulator before the all-gather — both phases cost
    ~1 B/element + scales, ~2/8 of the stock fp32 wire at large n."""
    n, size = 4, 1 << 20
    ring = _wire_bytes(n, size, "int8_ring")
    rs_int8 = (n - 1) / n * size + (n - 1) * 4   # int8 chunks + fp32 scales
    ag_int8 = (n - 1) / n * size + (n - 1) * 4   # int8 gather + fp32 scales
    assert ring == int(rs_int8 + ag_int8)
    stock = _wire_bytes(n, size, "stock")
    assert 0.24 * stock < ring < 0.26 * stock    # ~2/8 of stock
    # matches the a2a formulation exactly (same payload+scale schedule)
    assert ring == _wire_bytes(n, size, "int8_a2a")


def test_wire_bytes_int8_pairwise_models_full_payload_hops():
    """``pairwise_int8_allreduce`` never chunks: each of the n-1 hops ships
    the whole int8 payload plus one rowwise fp32 scale."""
    n, size = 4, 1 << 20
    pw = _wire_bytes(n, size, "int8_pairwise")
    assert pw == int((n - 1) * (size + 4))
    # cheaper than the fp32 wire at small n, worse than the chunked int8
    # forms at large n — the crossover the planner cares about
    assert pw < _wire_bytes(n, size, "stock")
    assert pw > _wire_bytes(n, size, "int8_ring")


# ---------------------------------------------------------------------------
# planner consumes the Record stream end-to-end (through JSONL)
# ---------------------------------------------------------------------------

def test_make_plan_from_record_stream_end_to_end():
    from repro.core import stressors
    recs = stressors.run_suite(duration=0.02,
                               names=["quant-int8", "vecmath", "allreduce"])
    buf = io.StringIO()
    write_jsonl(recs, buf)
    buf.seek(0)
    back = list(read_jsonl(buf))

    plan = planner.make_plan(RooflineTerms(0.01, 0.004, 0.02), back)
    assert plan.dp_method == "int8_a2a"  # collective-bound with headroom
    assert plan.ranking  # populated from the (non-skipped) records
    names = [n for n, _ in plan.ranking]
    assert "allreduce" not in names  # skipped records never ranked
    assert plan.serve_offload is None  # no serve stream provided


def test_planner_serve_offload_rule():
    """Rule 5: serve-side offload only while the probe headroom beside the
    engine clears the policy floor at every *sustained* load level."""
    from repro import runtime

    def hr(name, flops, sustained=True):
        return Record("serve.load_sweep", name, "headroom_flops_per_s",
                      flops, unit="flop/s",
                      params={"sustained": sustained})

    recs = [hr("probe_idle", 20e9),          # reference row, never a level
            hr("load_0.25x", 5e9), hr("load_1x", 2e9),
            hr("load_2x", 0.0, sustained=False)]   # past saturation
    a = planner.serve_offload_assessment(recs, min_headroom_flops=1e9)
    assert a["profitable"] and a["worst_headroom_flops"] == 2e9
    assert a["sustained_levels"] == ["load_0.25x", "load_1x"]
    assert not planner.serve_offload_assessment(
        recs, min_headroom_flops=3e9)["profitable"]

    # through make_plan, with the threshold from the runtime policy knob
    terms = RooflineTerms(0.01, 0.004, 0.02)
    assert planner.make_plan(terms, [], serve_records=recs).serve_offload
    with runtime.use_policy(serve_headroom_min_gflops=10.0):
        plan = planner.make_plan(terms, [], serve_records=recs)
    assert plan.serve_offload is False
    assert any("serve offload OFF" in n for n in plan.notes)

    # nothing sustained -> never profitable (rule 2: saturated engine)
    sat = [hr("load_2x", 9e9, sustained=False)]
    assert not planner.serve_offload_assessment(
        sat, min_headroom_flops=1e9)["profitable"]


def test_planner_serve_offload_slo_arm():
    """Rule 5, SLO arm: with ``serve.slo_sweep`` attainment rows in the
    stream, the highest-priority class must also make its SLO at every
    sustained level — probe headroom beside traffic that misses its
    targets is not sellable."""
    from repro import runtime

    def hr(name, flops, sustained=True):
        return Record("serve.slo_sweep", name, "headroom_flops_per_s",
                      flops, unit="flop/s",
                      params={"sustained": sustained})

    def att(name, v, rank, cls, sustained=True):
        return Record("serve.slo_sweep", name, "slo_attainment", v,
                      unit="fraction",
                      params={"rank": rank, "slo_class": cls,
                              "sustained": sustained})

    head = [hr("probe_idle", 20e9), hr("load_1x", 5e9),
            hr("load_4x", 4e9, sustained=False)]
    good = head + [att("slo_interactive_1x", 0.95, 0, "interactive"),
                   att("slo_batch_1x", 0.2, 1, "batch"),  # never gates
                   att("slo_interactive_4x", 0.1, 0, "interactive",
                       sustained=False)]  # saturated level excluded
    a = planner.serve_offload_assessment(good, min_headroom_flops=1e9)
    assert a["profitable"] and a["slo_ok"] is True
    assert a["slo_class"] == "interactive"
    assert a["worst_slo_attainment"] == 0.95
    assert a["slo_levels"] == {"slo_interactive_1x": 0.95}

    # the top class missing its SLO at a sustained level vetoes the
    # headroom verdict outright
    bad = head + [att("slo_interactive_1x", 0.5, 0, "interactive")]
    b = planner.serve_offload_assessment(bad, min_headroom_flops=1e9)
    assert b["slo_ok"] is False and not b["profitable"]
    assert b["worst_headroom_flops"] == 5e9  # headroom alone had cleared

    # no sustained attainment evidence -> tri-state None, verdict
    # falls back to the headroom floor alone
    none = head + [att("slo_interactive_4x", 0.1, 0, "interactive",
                       sustained=False)]
    c = planner.serve_offload_assessment(none, min_headroom_flops=1e9)
    assert c["slo_ok"] is None and c["profitable"]

    # through make_plan: the note names the arm and the class, and the
    # floor comes from the serve_slo_attainment_min policy knob
    terms = RooflineTerms(0.01, 0.004, 0.02)
    plan = planner.make_plan(terms, [], serve_records=bad)
    assert plan.serve_offload is False
    assert any("SLO arm FAILED" in n and "interactive" in n
               for n in plan.notes)
    assert any("offload withheld" in n for n in plan.notes)
    ok_plan = planner.make_plan(terms, [], serve_records=good)
    assert ok_plan.serve_offload is True
    assert any("SLO arm OK" in n for n in ok_plan.notes)
    with runtime.use_policy(serve_slo_attainment_min=0.99):
        strict = planner.make_plan(terms, [], serve_records=good)
    assert strict.serve_offload is False


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_lives_where_the_environment_says(tmp_path, from_env):
    """``runtime.enable_compile_cache`` (called by every entry point)
    leaves a set ``JAX_COMPILATION_CACHE_DIR`` to JAX, and otherwise puts
    the cache at ``<checkout>/.jax_cache``.  Subprocess: it changes the
    process-wide jax config."""
    import os
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    probe = "print(runtime.enable_compile_cache())"
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        # and an entry lands there
        probe += ("; jax.config.update("
                  "'jax_persistent_cache_min_compile_time_secs', 0)"
                  "; jax.jit(lambda x: x * 2)(jax.numpy.ones(3))")
    out = subprocess.run(
        [sys.executable, "-c", "import jax; from repro import runtime; "
         + probe], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == want
    if from_env:
        assert os.listdir(want)
