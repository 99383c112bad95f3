"""Latent attention, drop-free routed experts and a leading dense layer
(Moonlight-16B-A3B's block) on the normal serving path, against the plain
float32 reference (``perfbench/reference/mla_moe_transformer.py``), at
smoke size on the CPU with seeded random weights.

Float32 throughout: the program's serving path against the reference's
``highest``-precision forward agrees to ~1e-5 in the logits (summation
order); ``LOGIT_TOL`` sits two decades above that and a decade below what
bfloat16 matmuls give (``test_tolerance_catches_bfloat16``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import mla_moe_transformer as ref
from repro.configs import all_archs, smoke
from repro.models import mla, moe, registry
from repro.serve import paged
from repro.serve.continuous import ContinuousEngine
from repro.serve.scheduler import ServeRequest
from repro.serve.step import make_cells

# f32 program vs f32 reference: summation order only (~1e-5 observed);
# bf16 matmuls put the logits ~1e-2 off
LOGIT_TOL = 1e-3


def _cfg(dtype="float32"):
    return dataclasses.replace(smoke(all_archs()["moonlight-16b-a3b"]),
                               dtype=dtype)


def _served_logits(cfg, params, seqs, prompt_lens, cache_len=32, bs=8):
    """Each sequence prefilled (its prompt) into its own slot of the
    engine's cells (paged: the config is all attention), then decoded
    teacher-forced over its remaining tokens, all slots in one batch: the
    logits of every position from the last prompt token on, per
    sequence."""
    n = len(seqs)
    max_pages = cache_len // bs
    cells = make_cells(cfg, n, cache_len, bs, n * max_pages + 1)
    assert cells.paged
    pool = cells.init_state()
    tables = np.arange(n * max_pages, dtype=np.int32).reshape(n, max_pages)
    out = [[] for _ in seqs]
    for s, (seq, P) in enumerate(zip(seqs, prompt_lens)):
        logits, caches = cells.prefill(params, jnp.asarray(seq[None, :P]))
        out[s].append(np.asarray(logits[0, -1]))
        pool = cells.insert(pool, caches, jnp.asarray(tables[s]))
    idx = np.asarray(prompt_lens, np.int32)
    for _ in range(max(len(q) - P for q, P in zip(seqs, prompt_lens)) - 1):
        tok = np.asarray([q[min(i, len(q) - 1)] for q, i in zip(seqs, idx)],
                         np.int32)
        logits, pool, load = cells.decode(params, jnp.asarray(tok)[:, None],
                                          jnp.asarray(idx), pool,
                                          jnp.asarray(tables))
        for s, q in enumerate(seqs):
            if idx[s] < len(q) - 1:
                out[s].append(np.asarray(logits[s, 0]))
        idx = idx + 1
    return [np.stack(o) for o in out], load


def _seqs(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]


def _max_err(cfg, key):
    params = registry.init_params(cfg, key)
    seqs = _seqs(cfg, (20, 27, 12))
    prompts = (9, 15, 4)
    got, _ = _served_logits(cfg, params, seqs, prompts)
    err = 0.0
    for seq, P, g in zip(seqs, prompts, got):
        want = ref.logits(dataclasses.asdict(cfg), key, seq)[P - 1:-1]
        assert g.shape == want.shape
        err = max(err, float(np.max(np.abs(g - want))))
    return err


def test_paged_prefill_then_decode_matches_reference():
    """Expanded prefill, page insertion, then absorbed decode through the
    latent pages, three ragged sequences in one batch: every logit from
    the last prompt token on matches the reference's full forward."""
    assert _max_err(_cfg(), jax.random.key(5)) < LOGIT_TOL


def test_tolerance_catches_bfloat16():
    """The same comparison with the program's matmuls in bfloat16 (the
    reference rounds its weights alike) misses the tolerance: it is tight
    enough to catch a precision drop."""
    assert _max_err(_cfg("bfloat16"), jax.random.key(5)) > 10 * LOGIT_TOL


def test_absorbed_decode_matches_expanded_prefill():
    """One latent-attention layer: the absorbed one-token steps over a row
    cache give, position by position, the expanded form's outputs."""
    cfg = _cfg()
    p = mla.mla_init(jax.random.key(2), cfg)
    S = 12
    x = jax.random.normal(jax.random.key(3), (2, S, cfg.d_model))
    want = mla.mla_apply(cfg, p, x, positions=jnp.arange(S))
    cache = mla.init_cache(cfg, 2, 16)
    for t in range(S):
        y, cache = mla.mla_decode(cfg, p, x[:, t:t + 1], cache, index=t)
        assert float(jnp.max(jnp.abs(y[:, 0] - want[:, t]))) < 1e-5, t
    assert cache["ckv"].shape == (2, 16, mla.row_width(cfg))


def test_routing_is_drop_free():
    """A sequence's logits do not depend on its batch companions, however
    many tokens they send to the same experts; and the expert layer's
    output for a token is the same alone or among 64."""
    cfg = _cfg()
    params = registry.init_params(cfg, jax.random.key(7))
    a, b, c = _seqs(cfg, (20, 24, 18), seed=4)
    alone, _ = _served_logits(cfg, params, [a], [6])
    crowd, load = _served_logits(cfg, params, [a, b, c], [6, 9, 3])
    assert np.max(np.abs(alone[0] - crowd[0])) < 1e-5
    assert load.shape == (cfg.num_groups(), cfg.num_experts)

    pm = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["l0"])["moe"]
    x = jax.random.normal(jax.random.key(8), (1, 64, cfg.d_model))
    y, load = moe.moe_serve(cfg, pm, x)
    assert int(load.sum()) == 64 * cfg.experts_per_token
    for i in (0, 17, 63):
        yi, _ = moe.moe_serve(cfg, pm, x[:, i:i + 1])
        assert float(jnp.max(jnp.abs(yi[0, 0] - y[0, i]))) < 1e-5


def test_dead_tokens_cost_no_expert_work():
    """Tokens marked not live are routed to no expert: the load counts
    only the live ones, and their output is the shared experts' alone."""
    from repro.models import mlp
    cfg = _cfg()
    params = registry.init_params(cfg, jax.random.key(9))
    pm = jax.tree_util.tree_map(lambda w: w[0], params["layers"]["l0"])["moe"]
    x = jax.random.normal(jax.random.key(10), (4, 1, cfg.d_model))
    live = jnp.asarray([True, False, True, False])
    y, load = moe.moe_serve(cfg, pm, x, live=live)
    assert int(load.sum()) == 2 * cfg.experts_per_token
    shared = mlp.mlp_apply(cfg, pm["shared_mlp"], x)
    assert float(jnp.max(jnp.abs(y[1] - shared[1]))) < 1e-6
    full, _ = moe.moe_serve(cfg, pm, x)
    assert float(jnp.max(jnp.abs(y[0] - full[0]))) < 1e-6


def test_noaux_tc_bias_selects_but_does_not_weigh():
    """The correction bias changes which experts are picked, not how they
    are weighed: the gates are the picked experts' unbiased sigmoid
    scores, normalised to 1 and scaled by ``routed_scaling_factor``."""
    cfg = dataclasses.replace(_cfg(), num_experts=8, experts_per_token=2)
    p = moe.moe_init(jax.random.key(11), cfg)
    x = jax.random.normal(jax.random.key(12), (32, cfg.d_model))
    p0 = dict(p, score_bias=jnp.zeros(8))
    g0, i0, scores, _ = moe.route(cfg, p0, x)
    np.testing.assert_allclose(jnp.sum(g0, -1), 2.446, rtol=1e-6)
    want = jnp.take_along_axis(scores, i0, -1)
    np.testing.assert_allclose(
        g0, want / want.sum(-1, keepdims=True) * 2.446, rtol=1e-6)
    # a bias that favours expert 3: picked by every token, weighed by its
    # own score
    p3 = dict(p, score_bias=jnp.zeros(8).at[3].set(10.0))
    g3, i3, scores3, _ = moe.route(cfg, p3, x)
    assert bool((i3 == 3).any(-1).all())
    assert not bool((i0 == 3).any(-1).all())
    np.testing.assert_allclose(scores3, scores)
    want3 = jnp.take_along_axis(scores, i3, -1)
    np.testing.assert_allclose(
        g3, want3 / want3.sum(-1, keepdims=True) * 2.446, rtol=1e-6)


def test_paged_engine_serves_reference_tokens():
    """The continuous paged engine with latent pages and drop-free
    experts: every served token is the reference's best (gaps ~0 in f32),
    each step logs the experts hit, and the pool is recycled."""
    cfg = _cfg()
    key = jax.random.key(13)
    params = registry.init_params(cfg, key)
    eng = ContinuousEngine(cfg, params, n_slots=3, cache_len=48,
                           block_size=8, debug=True)
    assert eng.paged
    reqs = [ServeRequest(prompt=s, max_new_tokens=n) for s, n in
            zip(_seqs(cfg, (5, 12, 9, 3), seed=6), (7, 4, 9, 5))]
    eng.run(reqs)
    assert eng.kv.n_free == eng.kv.n_blocks
    hits = [ev.experts_hit for ev in eng.step_log if ev.decoded]
    assert hits and all(1 <= h <= cfg.num_experts for h in hits)
    assert all(ev.expert_load_max >= 1 for ev in eng.step_log if ev.decoded)
    out = ref.gaps(dataclasses.asdict(cfg), key,
                   [(r.prompt, r.generated) for r in reqs])
    assert max(float(g.max()) for g, _ in out) < LOGIT_TOL


def test_pool_holds_latent_pages():
    cfg = _cfg()
    pool = jax.eval_shape(lambda: paged.init_kv_pool(cfg, 9, 8))
    width = paged.latent_width(cfg)
    assert width % 128 == 0 and width >= mla.row_width(cfg)
    assert pool["l0"].shape == (cfg.num_groups(), 9, 8, width)
    assert pool["dense"].shape == (cfg.first_k_dense_replace, 9, 8, width)
    geo = paged.pool_geometry(cfg, 9, 8)
    assert geo["state_kind"] == "latent"
    assert geo["page_bytes"] == cfg.num_layers * 8 * width * 4


def test_latent_attention_refuses_tensor_parallelism():
    with pytest.raises(ValueError, match="latent attention"):
        paged.check_paged(_cfg(), 64, 8, tp_size=2)


def test_dense_engine_serves_the_paged_engines_tokens():
    """The engine, built with no residency asked for, keeps the latent
    rows in pages and serves the reference's best token at every
    position of equal-budget requests."""
    cfg = _cfg()
    key = jax.random.key(14)
    params = registry.init_params(cfg, key)
    eng = ContinuousEngine(cfg, params, n_slots=3, cache_len=48,
                           block_size=8)
    assert eng.paged and eng.cells.geometry["state_kind"] == "latent"
    reqs = [ServeRequest(prompt=s, max_new_tokens=6)
            for s in _seqs(cfg, (5, 12, 9, 3), seed=15)]
    eng.run(reqs)
    assert all(len(r.generated) == 6 for r in reqs)
    out = ref.gaps(dataclasses.asdict(cfg), key,
                   [(r.prompt, r.generated) for r in reqs])
    assert max(float(g.max()) for g, _ in out) < LOGIT_TOL


def test_grouped_moe_layers_count_experts_per_layer():
    """A scanned group of two MoE layers (qwen3-moe's layout, softmax
    router) on the paged engine: each layer's expert load is its own,
    and every step logs at most ``num_experts`` experts hit."""
    cfg = dataclasses.replace(smoke(all_archs()["qwen3-moe-235b-a22b"]),
                              dtype="float32")
    assert cfg.layer_group == 2
    params = registry.init_params(cfg, jax.random.key(16))
    crowd, load = _served_logits(cfg, params, _seqs(cfg, (14, 11)), (5, 7))
    assert load.shape == (cfg.num_groups(), 2, cfg.num_experts)
    assert (load.sum(-1) == 2 * cfg.experts_per_token).all()
