"""The benchmark's yardstick on its own: traffic, latency arithmetic,
operation and byte counts (CPU, no engine)."""
from __future__ import annotations

import types
from collections import Counter

import numpy as np
import pytest

from perfbench import cost, spec, traffic
from perfbench.metrics import itl_p99_ms, ttft_p90_ms


def _config(name):
    return spec.config(spec.load(), name)


# -- traffic -----------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat", "decode"])
def test_every_seed_gets_the_same_work_in_another_order(mix):
    m = spec.traffic(mix)
    a = traffic.requests(m, 50304, 1, 40.0)
    b = traffic.requests(m, 50304, 2 ** 33 + 5, 40.0)
    assert len(a) == len(b)
    # one schedule for every seed: arrivals and sizes, request by request;
    # the seed draws the tokens
    assert [(t, len(p), n) for t, p, n in a] \
        == [(t, len(p), n) for t, p, n in b]
    assert not all(np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    # the sizes are the lognormal's quantiles, not one repeated size
    assert len(Counter(len(p) for _, p, _ in a)) > 1
    assert len(Counter(n for _, _, n in a)) > 1
    again = traffic.requests(m, 50304, 1, 40.0)
    assert all(x[0] == y[0] and np.array_equal(x[1], y[1]) and x[2] == y[2]
               for x, y in zip(a, again))
    for _, p, n in a:
        assert len(p) in m["prompt_len"]["buckets"]
        assert n in m["output_len"]["buckets"]
        assert p.dtype == np.int32 and 0 <= p.min() and p.max() < 50304


def test_open_mix_count_rate_and_window():
    m = {"kind": "open", "rate_rps": 3.2, "drain_s": 1,
         "prompt_len": {"median": 384, "sigma": 0.8, "buckets": [128, 512]},
         "output_len": {"median": 64, "sigma": 0.5, "buckets": [64]}}
    t = np.asarray([r[0] for r in traffic.requests(m, 100, 7, 40.0)])
    assert len(t) == 128
    assert t[0] == 0.0 and t[-1] < 40.0 and np.all(np.diff(t) >= 0)
    # the quantile gaps keep the exponential's spread
    g = np.diff(t)
    assert 0.6 < g.std() / g.mean() < 1.2


def test_backlog_is_queued_at_t0():
    m = {"kind": "backlog", "requests": 24,
         "prompt_len": {"median": 256, "sigma": 0.5, "buckets": [128, 256]},
         "output_len": {"median": 512, "sigma": 0.5, "buckets": [512]}}
    rows = traffic.requests(m, 1000, 5, 10.0)
    assert len(rows) == 24 and all(t == 0.0 for t, _, _ in rows)


def test_snap_is_nearest_by_log_distance():
    assert traffic.snap(700, [128, 256, 512, 1024, 1536]) == 512
    assert traffic.snap(730, [128, 256, 512, 1024, 1536]) == 1024
    assert traffic.snap(1300, [1024, 1536]) == 1536


# -- latency from stamps and the step log ------------------------------------

def _req(arrival, first):
    return types.SimpleNamespace(ttft_s=None if first is None
                                 else first - arrival)


def test_ttft_p90_from_due_time():
    run = types.SimpleNamespace(
        traffic={"kind": "open"},
        requests=[_req(i * 1.0, i * 1.0 + 0.010 * (i + 1)) for i in range(10)]
        + [_req(20.0, None)])
    # ttft 10..100 ms; numpy's linear 90th percentile of ten points
    assert ttft_p90_ms.read(run) == pytest.approx(91.0)
    run.traffic = {"kind": "backlog"}
    assert ttft_p90_ms.read(run) is None


def test_inter_token_gaps_from_loop_tops():
    ev = types.SimpleNamespace
    log = [ev(now=0.00, decoded=(0,)),
           ev(now=0.01, decoded=(0, 1)),
           ev(now=0.05, decoded=(0, 1)),      # a prefill stalled this one
           ev(now=0.06, decoded=(1,)),
           ev(now=0.08, decoded=(2,))]
    assert itl_p99_ms.gaps(log) == pytest.approx([0.01, 0.04, 0.04, 0.01])
    run = types.SimpleNamespace(traffic={"kind": "open"}, step_log=log)
    assert itl_p99_ms.read(run) == pytest.approx(
        np.percentile([10, 40, 40, 10], 99))


# -- operations and bytes, by hand --------------------------------------------

def test_olmo_1b_counts_by_hand():
    c = _config("olmo-1b")
    # per layer: q, k, v, o 4 x 2048 x 2048; SwiGLU 3 x 2048 x 8192
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert cost.layer_matmul_params(c) == per_layer == 67108864
    head = 2048 * 50304
    # 16 slots attending 1000 positions each
    want = 16 * (2 * 16 * per_layer + 2 * head) + 4 * 16 * 16 * 128 * 16000
    assert cost.decode_step_flops(c, 16, 16000) == want
    assert cost.paged_attn_flops(c, 16000) == 4 * 16 * 16 * 128 * 16000
    # K+V of 16000 positions: 2 x 16 heads x 128 x 2 bytes, 16 layers;
    # q in and out for 16 slots
    assert cost.paged_attn_bytes(c, 16, 16000) == 16 * (
        16000 * 2 * 16 * 128 * 2 + 16 * 2 * 16 * 128 * 2)


def test_mistral_nemo_stage_counts_by_hand():
    c = _config("mistral-nemo-12b-s8")
    # q and o: 5120 x 4096; k and v: 5120 x 1024 (8 kv heads of 128)
    per_layer = 2 * 5120 * 4096 + 2 * 5120 * 1024 + 3 * 5120 * 14336
    assert cost.layer_matmul_params(c) == per_layer == 272629760
    head = 5120 * 131072
    assert cost.decode_step_flops(c, 3, 3000) == (
        3 * (2 * 8 * per_layer + 2 * head) + 4 * 8 * 32 * 128 * 3000)
    # GQA: the kernel reads 8 K/V heads per position, not 32
    assert cost.paged_attn_bytes(c, 3, 3000) == 8 * (
        3000 * 2 * 8 * 128 * 2 + 3 * 2 * 32 * 128 * 2)
