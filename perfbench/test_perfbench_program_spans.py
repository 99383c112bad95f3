"""The engine's ``serve.*`` spans read beside the device's idle gaps
(``program_spans.py``), and the ``itl_clean_p99_ms`` reader, on traces
and step logs whose every interval is known."""
from __future__ import annotations

import types

import jax
import pytest

from perfbench import program_spans, spec, xplane
from perfbench.test_perfbench_xplane import (DEV, KERNEL, MS, OTHER, SPANS,
                                             _plane, chip_trace)

# the engine's spans over the device timeline of test_perfbench_xplane
# (decode [2,7), prefill [8,18), insert [18,20), decode [22,27), decode
# [29,34); idle [1,2) [7,8) [20,22) [27,29)), in ms
SERVE = [("serve.ingest", 1.2, 0.7, {"n": 2}),
         ("serve.sample", 2.5, 5.0, {"step": 0}),
         ("serve.book", 7.5, 0.3, {"step": 0}),
         ("serve.admit", 8.0, 13.0, {"rid": 5, "slot": 3,
                                     "prompt_tokens": 1024}),
         ("serve.prefill", 8.0, 0.5, {}),
         ("serve.first_token", 8.5, 9.7, {}),
         ("serve.insert", 18.2, 0.8, {}),
         ("serve.decode", 21.0, 1.0, {"step": 1, "active": 16}),
         ("serve.sample", 22.5, 5.5, {"step": 1}),
         ("serve.book", 28.0, 0.2, {"step": 1}),
         ("serve.decode", 28.2, 0.8, {"step": 2, "active": 15})]
STATS = ["active", "context", "tokens", "n", "step", "rid", "slot",
         "prompt_tokens"]


def profile(host_spans):
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(n, s * MS, d * MS, {}) for n, s, d in DEV]),
        ("XLA Ops", [(n, s * MS, d * MS, {}) for n, s, d in KERNEL + OTHER])],
        sorted({n for n, _, _ in DEV + KERNEL + OTHER}))
    host = _plane(2, "/host:CPU", [
        ("python", [(n, s * MS, d * MS, st) for n, s, d, st in host_spans])],
        sorted({n for n, *_ in host_spans}), STATS)
    return jax.profiler.ProfileData.from_text_proto(dev + "\n" + host)


def _run(tr):
    from perfbench import peaks
    return types.SimpleNamespace(trace=tr, config=spec.config(spec.load(),
                                                              "olmo-1b"),
                                 peaks=peaks.peaks("TPU v5 lite"))


READERS = ("decode_step_ms", "host_gap_ms", "prefill_ms_per_ktok",
           "idle_share", "decode_mfu", "paged_attn_roofline")


def test_engine_spans_leave_every_reader_unchanged():
    # the benchmark's reduction keeps its own bench.* spans only, so the
    # engine's spans move no window, no pairing and no reading
    bare = xplane.reduce(profile(SPANS))
    both = xplane.reduce(profile(SPANS + SERVE))
    assert both.window == bare.window
    assert [s.name for s in both.spans] == [s.name for s in bare.spans]
    for name in READERS:
        reader = spec.reader(name)
        assert reader.read(_run(both)) == reader.read(_run(bare)), name
    assert xplane.breakdown(both) == xplane.breakdown(bare)


def test_spans_are_read_with_their_arguments():
    evs = program_spans.spans(profile(SPANS + SERVE))
    assert [s.name for s in evs] == [n for n, *_ in SERVE]
    adm = evs[3]
    assert (adm.start, adm.end) == (8 * MS, 21 * MS)
    assert adm.stats == {"rid": 5, "slot": 3, "prompt_tokens": 1024}
    assert evs[7].stats == {"step": 1, "active": 16}


def test_gap_split_by_innermost_span():
    pd = profile(SPANS + SERVE)
    tr, evs = xplane.reduce(pd), program_spans.spans(pd)
    parts = program_spans.gap_split(tr, evs)
    ms = [{k: round(v / MS, 6) for k, v in p.items()} for p in parts]
    # decodes [2,7) -> [22,27): idle [7,8) and [20,22). Under sample
    # [7,7.5), book [7.5,7.8), nothing [7.8,8); under admit (its insert
    # ended at 19) [20,21), the next decode's dispatch [21,22)
    # decodes [22,27) -> [29,34): idle [27,29), split across sample
    # [27,28), book [28,28.2) and the next decode's dispatch [28.2,29)
    assert ms == [{"sync": 0.5, "host": 2.3, "none": 0.2},
                  {"sync": 1.0, "host": 1.0, "none": 0.0}]
    s = program_spans.summary(tr, evs)
    assert s["pairs"] == 2 and s["clock_offset_ms"] == 0.0
    assert s["gap_sync_ms"] == pytest.approx(0.75)
    assert s["gap_host_ms"] == pytest.approx(1.65)
    assert s["gap_none_ms"] == pytest.approx(0.1)
    # the whole gap is the one host_gap_ms reads
    assert s["host_gap_ms"] == pytest.approx(
        spec.reader("host_gap_ms").read(_run(tr)))
    assert s["span_counts"]["serve.sample"] == 2


def _late(spans, ms):
    return [(n, s + ms, d, st) for n, s, d, st in spans]


def test_host_clock_ahead_of_the_device():
    # the device's programs began before the host's launches ended: every
    # host span runs 1 ms ahead of the device, so the gaps are put on the
    # host's clock by that much and split as before
    pd = profile(_late(SPANS + SERVE, 1.0))
    tr, evs = xplane.reduce(pd), program_spans.spans(pd)
    assert program_spans.clock_offset(tr, evs) == pytest.approx(1.0 * MS)
    base = profile(SPANS + SERVE)
    want = program_spans.gap_split(xplane.reduce(base),
                                   program_spans.spans(base))
    got = program_spans.gap_split(tr, evs)
    assert [{k: round(v / MS, 6) for k, v in p.items()} for p in got] \
        == [{k: round(v / MS, 6) for k, v in p.items()} for p in want]
    # a host behind the device (launch latency) shifts nothing
    pd = profile(_late(SPANS + SERVE, -0.3))
    assert program_spans.clock_offset(xplane.reduce(pd),
                                      program_spans.spans(pd)) == 0.0


def test_idle_gaps_named_by_innermost_span():
    pd = profile(SPANS + SERVE)
    tr, evs = xplane.reduce(pd), program_spans.spans(pd)
    # longest first, each named by the span most of it fell under:
    # [20,22) admit 1.0 (then decode and the benchmark's decode 0.5
    # each); [27,29) sample 1.0; [1,2) ingest 0.7; [7,8) the benchmark's
    # prefill 0.5 (book 0.3, none 0.2)
    assert [[n, round(s * 1e3, 6)]
            for n, s in program_spans.labelled_gaps(tr, evs)] == [
        ["serve.admit", 2.0], ["serve.sample", 2.0],
        ["serve.ingest", 1.0], ["bench.prefill", 1.0]]
    # without engine spans, most of [20,22) and [27,29) is under none
    assert [n for n, _ in program_spans.labelled_gaps(tr, [])] == [
        "engine loop", "engine loop", "bench.decode", "bench.prefill"]


def test_admit_stall_per_ktok():
    evs = program_spans.spans(profile(SPANS + SERVE))
    assert program_spans.admit_stall_ms_per_ktok(evs) \
        == pytest.approx(13.0 / 1.024)
    assert program_spans.admit_stall_ms_per_ktok(evs[:3]) is None


def test_chip_slice_without_engine_spans():
    # the committed chip slice predates the engine's spans: every gap
    # between decodes is under none of them
    tr = chip_trace()
    s = program_spans.summary(tr, [])
    assert s["pairs"] == 3
    assert s["gap_sync_ms"] == s["gap_host_ms"] == 0.0
    assert s["gap_none_ms"] == pytest.approx(s["host_gap_ms"])
    assert s["host_gap_ms"] == pytest.approx(
        spec.reader("host_gap_ms").read(_run(tr)))


def _req(*t):
    return types.SimpleNamespace(token_t=list(t))


def _ev(now, admitted=()):
    return types.SimpleNamespace(now=now, admitted=tuple(admitted))


def test_itl_clean_leaves_out_gaps_that_hold_an_admission():
    reader = spec.reader("itl_clean_p99_ms")
    # admissions began at 0.25 (inside r0's 0.2->0.3 and r1's
    # 0.21->0.31) and at 0.5 (inside r1's 0.43->0.6); 0.1 admitted none
    log = [_ev(0.1), _ev(0.25, [7]), _ev(0.5, [8])]
    reqs = [_req(0.1, 0.2, 0.3, 0.35), _req(0.21, 0.31, 0.43, 0.6)]
    clean, stalled = reader.gaps(reqs, log)
    assert [round(g, 9) for g in clean] == [0.1, 0.05, 0.12]
    assert [round(g, 9) for g in stalled] == [0.1, 0.1, 0.17]
    run = types.SimpleNamespace(requests=reqs, step_log=log,
                                traffic={"kind": "open"})
    # p99 of 0.05, 0.1, 0.12: 98% of the way from 0.1 to 0.12
    assert reader.read(run) == pytest.approx(1e3 * (0.1 + 0.98 * 0.02))
    # a backlog reports no ITL; a program without token_t gives nothing
    assert reader.read(types.SimpleNamespace(
        requests=reqs, step_log=log, traffic={"kind": "backlog"})) is None
    assert reader.read(types.SimpleNamespace(
        requests=[types.SimpleNamespace(generated=[1, 2])], step_log=log,
        traffic={"kind": "open"})) is None
