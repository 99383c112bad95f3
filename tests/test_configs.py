"""Config system: exact assigned dims, smoke reductions, shape skip rule."""
import pytest

from repro.configs import all_archs, live_shapes, smoke
from repro.configs.base import SHAPES

EXPECTED = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)
    "command-r-plus-104b": (64, 12288, 96, 8, 33792, 256000),
    "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
    "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
    "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
    "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
    "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
    "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
    "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
    "whisper-base": (6, 512, 8, 8, 2048, 51865),
    "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
}


def test_all_ten_archs_registered():
    assert set(all_archs()) == set(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_exact_dims(name):
    c = all_archs()[name]
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == EXPECTED[name]


def test_moe_configs():
    q = all_archs()["qwen3-moe-235b-a22b"]
    assert (q.num_experts, q.experts_per_token) == (128, 8)
    m = all_archs()["moonlight-16b-a3b"]
    assert (m.num_experts, m.experts_per_token, m.shared_experts) == (64, 6, 2)
    assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim) == (512, 128, 64, 128)
    assert (m.first_k_dense_replace, m.dense_d_ff) == (1, 11264)
    assert (m.router_scoring, m.routed_scaling_factor) == ("sigmoid", 2.446)
    assert (m.tie_embeddings, m.rope_theta, m.norm_eps) == (False, 5e4, 1e-5)
    assert m.num_groups() == 26
    s = smoke(m)
    assert s.first_k_dense_replace == 1 and s.num_groups() >= 2
    assert s.kv_lora_rank != s.qk_rope_head_dim
    j = all_archs()["jamba-1.5-large-398b"]
    assert (j.num_experts, j.experts_per_token, j.attn_period) == (16, 2, 8)


def test_long_context_skip_rule():
    # sub-quadratic archs run long_500k; pure full attention skips it
    runs_500k = {n for n, c in all_archs().items()
                 if any(s.name == "long_500k" for s in live_shapes(c))}
    assert runs_500k == {"h2o-danube-3-4b", "jamba-1.5-large-398b", "rwkv6-7b"}


def test_cells_count():
    total = sum(len(live_shapes(c)) for c in all_archs().values())
    assert total == 33  # 10 archs x 4 shapes - 7 full-attention long_500k skips


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_smoke_reduction_is_same_family(name):
    c = all_archs()[name]
    s = smoke(c)
    assert s.family == c.family
    assert bool(s.num_experts) == bool(c.num_experts)
    assert bool(s.attn_period) == bool(c.attn_period)
    assert s.d_model <= 64 and s.vocab_size <= 512
