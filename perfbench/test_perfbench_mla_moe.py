"""The latent-attention MoE cell's yardstick: ``cost_mla_moe`` against
hand arithmetic at the cell's widths, and its four readers on a trace and
a ``step_log`` whose every number is known."""
from __future__ import annotations

import types

import jax
import pytest

from perfbench import cost_mla_moe as cm
from perfbench import peaks, spec, xplane
from perfbench.test_perfbench_xplane import MS, _plane

CELL = "moonlight16b.decode"


def _config():
    return spec.config(spec.load(), "moonlight-16b-a3b-s9")


def test_cost_at_the_cells_widths():
    c = _config()
    assert cm.row(c) == 576 and cm.moe_layers(c) == 8
    # one expert: gate, up and down of 2048 x 1408, 17.3 MB in bf16
    assert cm.expert_params(c) * 2 == 17_301_504
    # 9 layers x 16 heads x (576 scored + 512 summed) x 2, per position
    assert cm.mla_attn_flops(c, 1) == 313_344
    # 1000 rows of 576 bf16 values a layer, plus one slot's query (576)
    # and output (512) for each of 16 heads
    assert cm.mla_attn_bytes(c, 1, 1000) == 9 * (1000 * 1152 + 16 * 1088 * 2)
    # 8 MoE layers x 32 tokens x 6 experts x 3 x 2048 x 1408 x 2
    assert cm.expert_gmm_flops(c, 32) == 8 * 32 * 6 * 8_650_752 * 2
    # the issue's sum: ~61.3 experts hit of 64 at 32 busy slots stream
    # ~8.5 GB a step; the rows moved add 32 x 6 x (2 x 2048 + 3 x 1408) x 2
    b = cm.expert_gmm_bytes(c, 32, 61.3)
    assert b == pytest.approx(8 * (61.3 * 17_301_504 + 192 * 8320 * 2))
    assert 8.4e9 < b < 8.6e9
    # per token: 9 x absorbed attention (q 2048x16x192, kv_a 2048x576,
    # into the latent 16x128x512, out 16x512x128, o 16x128x2048), the
    # dense MLP (3 x 2048 x 11264), 8 x (router 2048 x 64 + 8 experts),
    # the head 2048 x 163840; 2 operations a multiply-add
    attn = (6_291_456 + 1_179_648 + 1_048_576 + 1_048_576 + 4_194_304)
    want = 2 * (9 * attn + 69_206_016 + 8 * (131_072 + 8 * 8_650_752)
                + 335_544_320)
    assert cm.token_flops(c) == want == 2_166_620_160
    assert cm.decode_step_flops(c, 32, 27_000) \
        == 32 * want + cm.mla_attn_flops(c, 27_000)


MLA = ('%paged_mla_attention.3 = bf16[32,16,512]{2,1,0} custom-call(), '
       'custom_call_target=\\"tpu_custom_call\\"')
GMM = ('%expert_gmm.31 = f32[192,1408]{1,0} custom-call(), '
       'custom_call_target=\\"tpu_custom_call\\"')
PAGED = ('%paged_attention.7 = bf16[16,16,128]{2,1,0} custom-call(), '
         'custom_call_target=\\"tpu_custom_call\\"')
# device, in ms: decode [2,12) (latent kernel [3,4), gmm [5,8)), prefill
# [13,18), decode [20,30) (latent [21,22), gmm [23,26)); a gmm op of the
# prefill [14,15) does not count
DEV = [("jit_serve_decode(7)", 2, 10), ("jit_serve_prefill(8)", 13, 5),
       ("jit_serve_decode(7)", 20, 10)]
OPS = [(MLA, 3, 1), (GMM, 5, 3), (GMM, 14, 1), (MLA, 21, 1), (GMM, 23, 3)]
SPANS = [("bench.decode", 1, 0.5, {"active": 32, "context": 27000}),
         ("bench.prefill", 12.5, 0.3, {"tokens": 512}),
         ("bench.decode", 19, 0.5, {"active": 31, "context": 26000})]


def _trace(ops=OPS) -> xplane.Trace:
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(n, s * MS, d * MS, {}) for n, s, d in DEV]),
        ("XLA Ops", [(n, s * MS, d * MS, {}) for n, s, d in ops])],
        sorted({n for n, _, _ in DEV + ops}))
    host = _plane(2, "/host:CPU", [
        ("python", [(n, s * MS, d * MS, st) for n, s, d, st in SPANS])],
        sorted({n for n, *_ in SPANS}), ["active", "context", "tokens"])
    return xplane.reduce(jax.profiler.ProfileData.from_text_proto(
        dev + "\n" + host))


def _step(hit):
    return types.SimpleNamespace(decoded=(1,), experts_hit=hit,
                                 expert_load_max=9)


def _run(tr, step_log):
    return types.SimpleNamespace(trace=tr, config=_config(),
                                 step_log=step_log,
                                 peaks=peaks.peaks("TPU v5 lite"))


def test_cell_lists_its_metrics():
    bench = spec.load()
    names = [m["name"] for m in spec.metrics(bench, CELL, True)]
    for n in ("mla_attn_roofline.backlog", "expert_gmm_roofline.backlog",
              "experts_hit", "decode_mfu_moe.backlog", "slots_busy"):
        assert n in names
    assert "paged_attn_roofline.backlog" not in names
    assert "decode_mfu.backlog" not in names
    assert [m["name"] for m in spec.metrics(bench, CELL, False)] \
        == ["out_tok_s", "setup_s"]


def test_readers_on_a_known_trace():
    run = _run(_trace(), [_step(60.0), _step(62.0),
                          types.SimpleNamespace(decoded=(), experts_hit=None,
                                                expert_load_max=None)])
    read = {n: spec.reader(n).read(run) for n in (
        "experts_hit", "mla_attn_roofline", "expert_gmm_roofline",
        "decode_mfu_moe", "paged_attn_roofline")}
    c = run.config
    assert read["experts_hit"] == pytest.approx(61.0)
    nbytes = cm.mla_attn_bytes(c, 32, 27000) + cm.mla_attn_bytes(c, 31, 26000)
    assert read["mla_attn_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 2e-3)
    gb = cm.expert_gmm_bytes(c, 32, 61.0) + cm.expert_gmm_bytes(c, 31, 61.0)
    assert read["expert_gmm_roofline"] == pytest.approx(
        100 * (gb / 819e9) / 6e-3)
    flops = cm.decode_step_flops(c, 32, 27000) \
        + cm.decode_step_flops(c, 31, 26000)
    assert read["decode_mfu_moe"] == pytest.approx(
        100 * flops / (20e-3 * 197e12))
    # the K/V kernel's reader does not take the latent kernel for its own
    assert read["paged_attn_roofline"] is None


def test_readers_leave_out_what_the_run_lacks():
    """A program without the kernels or the counter (the parent's, or a
    dense configuration's) gives nothing to read, and no reader raises."""
    dense = _run(_trace([(PAGED, 3, 1), (PAGED, 21, 1)]),
                 [types.SimpleNamespace(decoded=(1,))])
    for n in ("experts_hit", "mla_attn_roofline", "expert_gmm_roofline"):
        assert spec.reader(n).read(dense) is None
    olmo = types.SimpleNamespace(trace=_trace(), step_log=[],
                                 config=spec.config(spec.load(), "olmo-1b"),
                                 peaks=peaks.peaks("TPU v5 lite"))
    assert spec.reader("decode_mfu_moe").read(olmo) is None
    untraced = _run(None, [_step(61.0)])
    assert spec.reader("expert_gmm_roofline").read(untraced) is None
    assert spec.reader("experts_hit").read(untraced) == 61.0


def test_reference_compares_where_its_own_precision_keeps_the_best():
    """``mla_moe_transformer.gaps`` compares a bfloat16 configuration's
    served positions where its bfloat16 forward keeps the float32 best
    token, the program's and the control's gaps at the same positions, and
    every position of a float32 configuration; a served token that is the
    float32 best reads 0, any other more."""
    import dataclasses

    import numpy as np

    from perfbench.reference import mla_moe_transformer as ref
    from repro.configs import all_archs, smoke
    c = dataclasses.asdict(smoke(all_archs()["moonlight-16b-a3b"]))
    assert c["dtype"] == ref.OWN
    key = jax.random.key(5)
    prompt = np.random.default_rng(5).integers(0, c["vocab_size"], 12)
    served = []                      # greedy under the float32 forward
    for _ in range(4):
        toks = list(prompt) + served
        served.append(int(ref.logits(c, key, toks)[-1].argmax()))
    (g, gl), = ref.gaps(c, key, [(prompt, served)], lower="int8")
    assert 1 <= len(g) == len(gl) <= len(served)
    assert not g.any()
    c32 = dict(c, dtype="float32")
    wrong = served[:-1] + [(served[-1] + 1) % c["vocab_size"]]
    (g32, none), = ref.gaps(c32, key, [(prompt, wrong)])
    assert none is None and len(g32) == len(served) and g32[-1] > 0
