"""The trace reduction and the per-layer readers built on it, on a trace
whose every interval is known (written as an XSpace text proto)."""
from __future__ import annotations

import gzip
import os
import types

import jax
import pytest

from perfbench import cost, peaks, spec, xplane
from perfbench.metrics import paged_attn_roofline as roof

MS = 1_000_000          # ns


def _plane(pid, name, lines, names, stats=()):
    ev_ids = {n: i + 1 for i, n in enumerate(names)}
    st_ids = {n: i + 1 for i, n in enumerate(stats)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for ev_name, start, dur, ev_stats in events:
            st = " ".join(f"stats {{ metadata_id: {st_ids[k]} "
                          f"int64_value: {v} }}" for k, v in ev_stats.items())
            out.append(f"events {{ metadata_id: {ev_ids[ev_name]} "
                       f"offset_ps: {int(start * 1000)} "
                       f"duration_ps: {int(dur * 1000)} {st} }}")
        out.append("}")
    for n, i in ev_ids.items():
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    for n, i in st_ids.items():
        out.append(f'stat_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


# device, in ms: decode [2,7) (kernel [3,5)), prefill [8,18), insert
# [18,20), decode [22,27) (kernel [23,25)), decode [29,34) (kernel
# [30,32)); each host span launches the program after it
DEV = [("jit__decode(7)", 2, 5), ("jit__prefill(8)", 8, 10),
       ("jit__insert(9)", 18, 2), ("jit__decode(7)", 22, 5),
       ("jit__decode(7)", 29, 5)]
PAGED = ('%_paged_attention.7 = bf16[16,16,128]{2,1,0} custom-call(), '
         'custom_call_target=\\"tpu_custom_call\\"')
KERNEL = [(PAGED, 3, 2), (PAGED, 23, 2), (PAGED, 30, 2),
          ('%custom-call.2 = bf16[16] custom-call(), '
           'custom_call_target=\\"AllocateBuffer\\"', 25, 0.5)]
OTHER = [("fusion.1", 2, 1), ("fusion.5", 5, 2), ("fusion.2", 8, 10),
         ("fusion.4", 18, 2), ("fusion.1", 22, 1), ("fusion.5", 25, 2),
         ("fusion.1", 29, 1), ("fusion.5", 32, 2)]
SPANS = [("bench.decode", 1, 1, {"active": 16, "context": 8000}),
         ("bench.prefill", 7, 0.8, {"tokens": 1024}),
         ("bench.insert", 17.5, 0.3, {}),
         ("bench.decode", 21, 0.5, {"active": 16, "context": 8016}),
         ("bench.decode", 28, 0.5, {"active": 15, "context": 7000})]


def trace() -> xplane.Trace:
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(n, s * MS, d * MS, {}) for n, s, d in DEV]),
        ("XLA Ops", [(n, s * MS, d * MS, {}) for n, s, d in KERNEL + OTHER])],
        sorted({n for n, _, _ in DEV + KERNEL + OTHER}))
    host = _plane(2, "/host:CPU", [
        ("python", [(n, s * MS, d * MS, st) for n, s, d, st in SPANS])],
        sorted({n for n, *_ in SPANS}), ["active", "context", "tokens"])
    pd = jax.profiler.ProfileData.from_text_proto(dev + "\n" + host)
    return xplane.reduce(pd)


def _run(tr, name="olmo-1b"):
    return types.SimpleNamespace(trace=tr, config=spec.config(spec.load(),
                                                              name),
                                 peaks=peaks.peaks("TPU v5 lite"))


def test_window_busy_and_idle():
    tr = trace()
    # from the first host span to the last device program
    assert tr.window == (1 * MS, 34 * MS)
    # busy: [2,7) [8,20) [22,27) [29,34) = 27 ms of 33
    assert tr.busy_s() == pytest.approx(27e-3)
    assert tr.idle_gaps(0) == [(1 * MS, 2 * MS), (7 * MS, 8 * MS),
                               (20 * MS, 22 * MS), (27 * MS, 29 * MS)]


def test_spans_pair_with_the_programs_they_launched():
    tr = trace()
    pairs = xplane.paired(tr.spans_named("bench.decode"),
                          tr.modules(0, "_decode"))
    assert [(s.stats["active"], m.start / MS) for s, m in pairs] \
        == [(16, 2), (16, 22), (15, 29)]


def test_readers_on_the_known_trace():
    tr = trace()
    run = _run(tr)
    read = {n: spec.reader(n).read(run) for n in (
        "decode_step_ms", "host_gap_ms", "prefill_ms_per_ktok",
        "idle_share", "decode_mfu", "paged_attn_roofline")}
    assert read["decode_step_ms"] == pytest.approx(5.0)
    # between decodes: 15 ms with 12 busy (prefill, insert), then 2 ms
    assert read["host_gap_ms"] == pytest.approx((3 + 2) / 2)
    assert read["prefill_ms_per_ktok"] == pytest.approx(12 / 1.024)
    assert read["idle_share"] == pytest.approx(100 * 6 / 33)
    c = run.config
    flops = (cost.decode_step_flops(c, 16, 8000)
             + cost.decode_step_flops(c, 16, 8016)
             + cost.decode_step_flops(c, 15, 7000))
    assert read["decode_mfu"] == pytest.approx(
        100 * flops / (15e-3 * 197e12))
    nbytes = (cost.paged_attn_bytes(c, 16, 8000)
              + cost.paged_attn_bytes(c, 16, 8016)
              + cost.paged_attn_bytes(c, 15, 7000))
    assert read["paged_attn_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 6e-3)


def test_breakdown_labels_idle_gaps_by_host_span():
    b = xplane.breakdown(trace())
    assert b["device_ops"][0] == ["jit__prefill/fusion.2",
                                  pytest.approx(10e-3)]
    assert b["device_ops"][1] == ["jit__decode/%_paged_attention.7",
                                  pytest.approx(6e-3)]
    # fusion.5 [25,27) holds the 0.5 ms custom call: its own time is less
    assert dict(b["device_ops"])["jit__decode/fusion.5"] \
        == pytest.approx(5.5e-3)
    # longest first; the device waited on the host's launch of each
    # program, and after the insert on the next decode's launch
    assert [[n, round(s * 1e3, 6)] for n, s in b["idle_gaps"]] == [
        ["bench.decode", 2.0], ["bench.decode", 2.0],
        ["bench.decode", 1.0], ["bench.prefill", 1.0]]


# a slice of a --trace 1 run of olmo1b.chat recorded on one TPU v5e: a
# decode step, a prefill of 256 tokens and its insert, three more steps
CHIP = os.path.join(os.path.dirname(__file__), "chip_trace_chat.xplane.pb.gz")


def chip_trace() -> xplane.Trace:
    with gzip.open(CHIP, "rb") as fh:
        return xplane.loads(fh.read())


def test_chip_trace_planes_programs_and_spans():
    tr = chip_trace()
    assert list(tr.devices) == [0]
    assert [s.name for s in tr.spans] == [
        "bench.decode", "bench.prefill", "bench.insert", "bench.decode",
        "bench.decode", "bench.decode"]
    assert tr.spans[1].stats["tokens"] == 256
    dec = tr.modules(0, "_decode")
    assert [round(m.dur / MS, 6) for m in dec] == [
        47.317151, 47.503351, 47.508643, 47.515723]
    pairs = xplane.paired(tr.spans_named("bench.decode"), dec)
    assert [(s.stats["active"], m) for s, m in pairs] == [
        (9, dec[0]), (10, dec[1]), (10, dec[2]), (10, dec[3])]
    # one paged-attention kernel call per layer of each step
    kern = [e for e in tr.devices[0]["ops"] if roof.is_kernel(e)]
    assert len(kern) == 4 * 16
    assert all(any(m.start <= k.start < m.end for m in dec) for k in kern)


def test_readers_on_the_chip_trace():
    tr = chip_trace()
    run = _run(tr)
    read = {n: spec.reader(n).read(run) for n in (
        "decode_step_ms", "prefill_ms_per_ktok", "decode_mfu",
        "paged_attn_roofline", "idle_share", "host_gap_ms")}
    assert read["decode_step_ms"] == pytest.approx(
        (47.317151 + 47.503351 + 47.508643 + 47.515723) / 4)
    # the 256-token prefill program, then its insert
    assert read["prefill_ms_per_ktok"] == pytest.approx(
        (5.96263 + 2.753117) / 0.256, rel=1e-6)
    for share in ("decode_mfu", "paged_attn_roofline", "idle_share"):
        assert 0 < read[share] < 100
    assert 0 < read["host_gap_ms"] < 5
    b = xplane.breakdown(tr)
    top = [n for n, _ in b["device_ops"]]
    # the loop's body operations, not the loop, lead: the pool's write,
    # copy and read in the decode step
    assert top[0].startswith("jit__decode/%")
    assert not any("while" in n for n in top[:3])
    assert sum(s for _, s in b["device_ops"]) <= tr.busy_s() + 1e-9
