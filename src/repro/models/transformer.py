"""Decoder-only LM assembly: dense / MoE / hybrid (Jamba) / SSM (RWKV-6).

Layers are stacked into groups of ``cfg.layer_group`` and scanned with
``lax.scan`` (stacked params, optional remat on the group body), so compile
time and HLO size are O(one group), while XLA cost analysis stays
trip-count-exact.  Heterogeneous interleaves (Jamba: 7 Mamba + 1 attention
per group, MoE every 2nd layer) are unrolled *within* the group, which is
what makes the group homogeneous across the scan.  Leading dense layers
(``first_k_dense_replace``: an MLP of ``dense_d_ff`` where the others hold
experts) are ``params["dense_layers"]``, stacked likewise and applied in
order before the scan; their caches are ``caches["dense"]``.  Attention
is latent (``models/mla.py``) when ``kv_lora_rank > 0``.

MoE layers drop no token on the serving paths (prefill, decode:
``moe.moe_serve``); the training forward keeps the capacity dispatch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention, common, mamba, mla, mlp, moe, rwkv6
from repro.parallel import sharding

ZERO_AUX = {"lb_loss": jnp.float32(0.0), "z_loss": jnp.float32(0.0)}


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------

def _layer_init(rng, cfg: ArchConfig, l: int, dense: bool = False) -> dict:
    """One layer's params; ``l`` is the position within a group; ``dense``
    marks a leading dense layer."""
    ks = jax.random.split(rng, 4)
    p: dict = {"norm1": common.norm_init(cfg)}
    if cfg.family == "ssm":
        p["rwkv"] = rwkv6.time_mix_init(ks[0], cfg)
        p["norm2"] = common.norm_init(cfg)
        p["cmlp"] = rwkv6.channel_mix_init(ks[1], cfg)
        return p
    if cfg.is_attn_layer(l):
        p["attn"] = (mla.mla_init if cfg.mla else attention.attn_init)(
            ks[0], cfg)
    else:
        p["mamba"] = mamba.mamba_init(ks[0], cfg)
    if not cfg.parallel_block:
        p["norm2"] = common.norm_init(cfg)
    if dense:
        p["mlp"] = mlp.mlp_init(ks[1], cfg, d_ff=cfg.dense_d_ff or None)
    elif cfg.is_moe_layer(l):
        p["moe"] = moe.moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp.mlp_init(ks[1], cfg)
    return p


def _group_init(rng, cfg: ArchConfig) -> dict:
    ks = jax.random.split(rng, cfg.layer_group)
    return {f"l{i}": _layer_init(ks[i], cfg, i) for i in range(cfg.layer_group)}


def init_params(cfg: ArchConfig, rng) -> dict:
    ks = jax.random.split(rng, 4)
    dt = common.dtype_of(cfg)
    p = {
        "embed": common.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "layers": common.stacked_init(ks[1], cfg.num_groups(),
                                      lambda r: _group_init(r, cfg)),
        "final_norm": common.norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(ks[2], cfg.d_model, cfg.vocab_size, dt)
    if cfg.first_k_dense_replace:
        p["dense_layers"] = common.stacked_init(
            ks[3], cfg.first_k_dense_replace,
            lambda r: _layer_init(r, cfg, 0, dense=True))
    return p


def dense_layer(stacked, j: int) -> dict:
    """Layer ``j`` of the stacked leading dense layers (or their caches)."""
    return jax.tree_util.tree_map(lambda a: a[j], stacked)


def stack(trees: list):
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)


# ---------------------------------------------------------------------------
# layer apply (full-sequence and decode variants)
# ---------------------------------------------------------------------------

def _layer_apply(cfg: ArchConfig, p: dict, l: int, x, positions, *,
                 cache_len=None, layer=None):
    """Full-sequence layer.  Returns (x, aux, cache_or_None).  ``layer``:
    the scan index the layer's stacked expert kernels are read at
    (``moe.split_experts``)."""
    aux = ZERO_AUX
    cache = None
    make_cache = cache_len is not None
    h = common.norm_apply(cfg, p["norm1"], x)
    if cfg.family == "ssm":
        y, st = rwkv6.time_mix_apply(cfg, p["rwkv"], h)
        x = sharding.constrain(x + y, "batch", "seq_sp", None)
        h2 = common.norm_apply(cfg, p["norm2"], x)
        y2, st2 = rwkv6.channel_mix_apply(cfg, p["cmlp"], h2)
        x = sharding.constrain(x + y2, "batch", "seq_sp", None)
        if make_cache:
            cache = {"tm": st, "cm": st2}
        return x, aux, cache
    if "attn" in p and cfg.mla:
        if make_cache:
            y, cache = mla.mla_apply(cfg, p["attn"], h, positions=positions,
                                     return_cache=True, cache_len=cache_len)
        else:
            y = mla.mla_apply(cfg, p["attn"], h, positions=positions)
    elif "attn" in p:
        window = cfg.sliding_window
        if make_cache:
            y, cache = attention.attn_apply(
                cfg, p["attn"], h, positions=positions, causal=True,
                window=window, return_cache=True, cache_len=cache_len)
        else:
            y = attention.attn_apply(cfg, p["attn"], h, positions=positions,
                                     causal=True, window=window)
    else:
        if make_cache:
            y, cache = mamba.mamba_apply(cfg, p["mamba"], h, return_state=True)
        else:
            y = mamba.mamba_apply(cfg, p["mamba"], h)
    if cfg.parallel_block:
        f, aux = _ffn(cfg, p, h, serving=make_cache, layer=layer)
        return sharding.constrain(x + y + f, "batch", "seq_sp", None), \
            aux, cache
    x = sharding.constrain(x + y, "batch", "seq_sp", None)
    h2 = common.norm_apply(cfg, p["norm2"], x)
    f, aux = _ffn(cfg, p, h2, serving=make_cache, layer=layer)
    return sharding.constrain(x + f, "batch", "seq_sp", None), aux, cache


def _ffn(cfg, p, h, serving: bool = False, layer=None):
    """The layer's MLP or MoE: drop-free experts when ``serving``."""
    if "moe" in p:
        if serving:
            return moe.moe_serve(cfg, p["moe"], h, layer=layer)[0], ZERO_AUX
        return moe.moe_apply(cfg, p["moe"], h)
    return mlp.mlp_apply(cfg, p["mlp"], h), ZERO_AUX


def _layer_decode(cfg: ArchConfig, p: dict, l: int, x, cache: dict, index):
    """One-token layer step.  Returns (x, new_cache)."""
    h = common.norm_apply(cfg, p["norm1"], x)
    if cfg.family == "ssm":
        y, st = rwkv6.time_mix_apply(cfg, p["rwkv"], h, state=cache["tm"])
        x = x + y
        h2 = common.norm_apply(cfg, p["norm2"], x)
        y2, st2 = rwkv6.channel_mix_apply(cfg, p["cmlp"], h2, state=cache["cm"])
        return x + y2, {"tm": st, "cm": st2}
    if "attn" in p and cfg.mla:
        y, new_cache = mla.mla_decode(cfg, p["attn"], h, cache, index=index)
    elif "attn" in p:
        y, new_cache = attention.attn_decode(cfg, p["attn"], h, cache,
                                             index=index,
                                             window=cfg.sliding_window)
    else:
        y, new_cache = mamba.mamba_decode(cfg, p["mamba"], h, cache)
    if cfg.parallel_block:
        f, _ = _ffn(cfg, p, h, serving=True)
        return x + y + f, new_cache
    x = x + y
    h2 = common.norm_apply(cfg, p["norm2"], x)
    f, _ = _ffn(cfg, p, h2, serving=True)
    return x + f, new_cache


# ---------------------------------------------------------------------------
# backbone: scan over groups
# ---------------------------------------------------------------------------

def _group_apply(cfg, gp, x, positions, cache_len=None, held=None, g=None):
    auxes = ZERO_AUX
    caches = {}
    for i in range(cfg.layer_group):
        p = gp[f"l{i}"]
        if held and f"l{i}" in held:
            p = dict(p, moe=dict(p["moe"], **held[f"l{i}"]))
        x, aux, cache = _layer_apply(cfg, p, i, x, positions,
                                     cache_len=cache_len, layer=g)
        auxes = jax.tree_util.tree_map(lambda a, b: a + b, auxes, aux)
        if cache_len is not None:
            caches[f"l{i}"] = cache
    return x, auxes, caches


def apply_backbone(cfg: ArchConfig, layers, x, positions, *,
                   remat: bool = False, cache_len=None, dense_layers=None):
    """x: (B, S, D) embeddings.  Returns (x, aux[, caches])."""
    dense_caches = []
    for j in range(cfg.first_k_dense_replace):
        x, _, cache = _layer_apply(cfg, dense_layer(dense_layers, j), 0, x,
                                   positions, cache_len=cache_len)
        dense_caches.append(cache)

    # serving: the routed experts' kernels stay stacked, read in place
    held = None
    if cache_len is not None and cfg.num_experts:
        layers, held = moe.split_experts(layers)

    def body(carry, inp):
        x, auxes = carry
        gp, g = inp if held else (inp, None)
        x = sharding.constrain(x, "batch", "seq_sp", None)
        if remat and cfg.remat != "none":
            pol = (None if cfg.remat == "full"
                   else jax.checkpoint_policies.dots_saveable)
            fn = jax.checkpoint(
                lambda gp, x: _group_apply(cfg, gp, x, positions)[:2],
                policy=pol)
            x, aux = fn(gp, x)
            caches = {}
        else:
            x, aux, caches = _group_apply(cfg, gp, x, positions,
                                          cache_len=cache_len, held=held,
                                          g=g)
        auxes = jax.tree_util.tree_map(lambda a, b: a + b, auxes, aux)
        return (x, auxes), caches

    xs = (layers, jnp.arange(cfg.num_groups())) if held else layers
    (x, auxes), caches = jax.lax.scan(body, (x, ZERO_AUX), xs)
    if cache_len is not None:
        if dense_caches:
            caches = dict(caches, dense=stack(dense_caches))
        return x, auxes, caches
    return x, auxes


def backbone_decode(cfg: ArchConfig, layers, x, caches, index,
                    dense_layers=None):
    """One-token step through all groups.  caches: stacked over groups
    (and the leading dense layers' under ``"dense"``)."""
    caches = dict(caches)
    dense = caches.pop("dense", None)
    new_dense = []
    for j in range(cfg.first_k_dense_replace):
        x, c = _layer_decode(cfg, dense_layer(dense_layers, j), 0, x,
                             dense_layer(dense, j), index)
        new_dense.append(c)

    def body(x, inp):
        gp, cache_g = inp
        new = {}
        for i in range(cfg.layer_group):
            x, new[f"l{i}"] = _layer_decode(cfg, gp[f"l{i}"], i, x,
                                            cache_g[f"l{i}"], index)
        return x, new

    x, new_caches = jax.lax.scan(body, x, (layers, caches))
    if new_dense:
        new_caches = dict(new_caches, dense=stack(new_dense))
    return x, new_caches


# ---------------------------------------------------------------------------
# public LM API
# ---------------------------------------------------------------------------

def _logits(cfg, params, x):
    if cfg.tie_embeddings:
        y = x @ params["embed"]["embedding"].T
    else:
        y = common.dense(params["lm_head"], x)
    return sharding.constrain(y.astype(jnp.float32), "batch", "seq", "vocab")


def forward(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
            remat: bool = False, extra_embeds: Optional[jax.Array] = None):
    """tokens: (B, S) -> logits (B, S[, +P], V) fp32, aux dict."""
    x = params["embed"]["embedding"][tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x = sharding.constrain(x, "batch", "seq_sp", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, aux = apply_backbone(cfg, params["layers"], x, positions, remat=remat,
                            dense_layers=params.get("dense_layers"))
    x = common.norm_apply(cfg, params["final_norm"], x)
    return _logits(cfg, params, x), aux


def init_decode_caches(cfg: ArchConfig, batch: int, cache_len: int):
    """Stacked (over groups) decode caches for every layer position."""
    def one_layer(l):
        if cfg.family == "ssm":
            H, dh = rwkv6._dims(cfg)
            return {
                "tm": {"shift": jnp.zeros((batch, 1, cfg.d_model),
                                          common.dtype_of(cfg)),
                       "wkv": jnp.zeros((batch, H, dh, dh), jnp.float32)},
                "cm": jnp.zeros((batch, 1, cfg.d_model), common.dtype_of(cfg)),
            }
        if cfg.is_attn_layer(l) and cfg.mla:
            return mla.init_cache(cfg, batch, cache_len)
        if cfg.is_attn_layer(l):
            ln = cfg.sliding_window or cache_len   # SWA: full ring always
            return attention.init_cache(cfg, batch, ln)
        return mamba.init_state(cfg, batch)

    def tiled(tree, n):
        return jax.tree_util.tree_map(
            lambda a: jnp.tile(a[None], (n,) + (1,) * a.ndim), tree)

    caches = tiled({f"l{i}": one_layer(i) for i in range(cfg.layer_group)},
                   cfg.num_groups())
    if cfg.first_k_dense_replace:
        caches["dense"] = tiled(one_layer(0), cfg.first_k_dense_replace)
    return caches


def prefill(cfg: ArchConfig, params: dict, tokens: jax.Array,
            extra_embeds: Optional[jax.Array] = None,
            cache_len: Optional[int] = None):
    """Full forward that also returns decode caches sized ``cache_len``."""
    x = params["embed"]["embedding"][tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x = sharding.constrain(x, "batch", "seq", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, aux, caches = apply_backbone(cfg, params["layers"], x, positions,
                                    cache_len=cache_len or x.shape[1],
                                    dense_layers=params.get("dense_layers"))
    x = common.norm_apply(cfg, params["final_norm"], x)
    return _logits(cfg, params, x[:, -1:]), caches


def decode_step(cfg: ArchConfig, params: dict, tokens: jax.Array,
                caches, index):
    """tokens: (B, 1); index: scalar position.  Returns (logits, caches)."""
    x = params["embed"]["embedding"][tokens]
    x, new_caches = backbone_decode(cfg, params["layers"], x, caches, index,
                                    params.get("dense_layers"))
    x = common.norm_apply(cfg, params["final_norm"], x)
    return _logits(cfg, params, x), new_caches
