"""Multi-head latent attention (MLA, DeepSeek-V2/V3), with ``q_lora_rank``
null: the query is a plain projection.

Each token's keys and values come from one cached row: the latent ``c =
RMSNorm(x W_dkv)`` (``kv_lora_rank`` wide) and the rotary key ``k_pe =
rope(x W_kr)`` (``qk_rope_head_dim``) shared by every head. ``kv_b``
expands the latent per head into ``k_nope`` and ``v``; queries are
``[q_nope, rope(q_pe)]``; the softmax scale is
``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``.

Two forms of one attention:

* expanded (training, prefill): ``k = [c W_uk, k_pe]``, ``v = c W_uv`` per
  head, then causal softmax attention as for any multi-head layer;
* absorbed (decode): ``W_uk`` folds into the query, ``q_lat = q_nope
  W_uk^T`` (``kv_lora_rank`` per head), so a head's score against a
  position is ``[q_lat, q_pe] · row`` and its output the weighted sum of
  the rows' latents, expanded by ``W_uv`` and projected by ``o``
  afterwards. Only the rows are cached, 576 values a token at
  DeepSeek-V3 widths against 4,096 for 16 heads of 128 keys and values.

The cache is the rows, ``{"ckv": (B, cache_len, kv_lora_rank +
qk_rope_head_dim)}``; the paged engine keeps them in pages
(``serve/paged.py``, ``kernels/paged_mla_attention.py``).

Departure from the published model, shared with the reference: the rotary
pairs are the first and second halves of the rope dimensions (as in
``common.apply_rope``), where HF DeepSeek-V3 rotates interleaved pairs;
with random weights that is a fixed permutation of the rope columns of
``q`` and ``kv_a``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common

NEG_INF = -1e30


def mla_init(rng, cfg: ArchConfig) -> dict:
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = common.dtype_of(cfg)
    ks = jax.random.split(rng, 4)
    return {"q": common.dense_init(ks[0], D, H * (dn + dr), dt),
            "kv_a": common.dense_init(ks[1], D, R + dr, dt),
            "kv_norm": {"scale": jnp.ones((R,), dt)},
            "kv_b": common.dense_init(ks[2], R, H * (dn + dv), dt),
            "o": common.dense_init(ks[3], H * dv, D, dt)}


def row_width(cfg: ArchConfig) -> int:
    """Values cached per token and layer: the latent and the rope key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def sm_scale(cfg: ArchConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def latent_rows(cfg: ArchConfig, p: dict, x, positions):
    """x (..., S, D) -> the cached rows (..., S, R + dr): the normed
    latent, then the rope key at ``positions``."""
    R = cfg.kv_lora_rank
    a = common.dense(p["kv_a"], x)
    cf = a[..., :R].astype(jnp.float32)
    c = cf * jax.lax.rsqrt(jnp.mean(cf * cf, -1, keepdims=True)
                           + cfg.norm_eps)
    c = (c * p["kv_norm"]["scale"].astype(jnp.float32)).astype(x.dtype)
    kpe = common.apply_rope(a[..., None, R:], positions,
                            cfg.rope_theta)[..., 0, :]
    return jnp.concatenate([c, kpe.astype(x.dtype)], -1)


def _queries(cfg: ArchConfig, p: dict, x, positions):
    """(q_nope (..., S, H, dn), roped q_pe (..., S, H, dr))."""
    H, dn = cfg.num_heads, cfg.qk_nope_head_dim
    q = common.dense(p["q"], x)
    q = q.reshape(q.shape[:-1] + (H, dn + cfg.qk_rope_head_dim))
    return q[..., :dn], common.apply_rope(q[..., dn:], positions,
                                          cfg.rope_theta)


def _kv_b(cfg: ArchConfig, p: dict):
    """``kv_b`` per head: (W_uk (R, H, dn), W_uv (R, H, dv))."""
    dn = cfg.qk_nope_head_dim
    w = p["kv_b"]["kernel"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                                    dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]


def mla_apply(cfg: ArchConfig, p: dict, x, *, positions,
              return_cache: bool = False, cache_len=None):
    """Expanded causal MLA over a whole sequence. x: (B, S, D);
    positions: (S,). Returns y (B, S, D) and, with ``return_cache``, the
    rows padded to ``cache_len`` positions."""
    B, S, _ = x.shape
    H, R = cfg.num_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_pe = _queries(cfg, p, x, positions)
    rows = latent_rows(cfg, p, x, positions)
    c, kpe = rows[..., :R], rows[..., R:]
    kv = common.dense(p["kv_b"], c).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = sm_scale(cfg)

    def chunk(_, inp):
        qn, qp, pos_q = inp                                    # (B,cq,H,.)
        sc = (jnp.einsum("bqhn,bshn->bhqs", qn, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bsr->bhqs", qp, kpe,
                           preferred_element_type=jnp.float32)) * scale
        ok = positions[None, :] <= pos_q[:, None]
        sc = jnp.where(ok[None, None], sc, NEG_INF)
        pr = jax.nn.softmax(sc, -1)
        return (), jnp.einsum("bhqs,bshv->bqhv", pr.astype(v.dtype), v)

    cq = S if S <= 1024 else 512
    assert S % cq == 0, (S, cq)
    n = S // cq
    split = lambda t: t.reshape((B, n, cq) + t.shape[2:]).swapaxes(0, 1)
    _, out = jax.lax.scan(chunk, (), (split(q_nope), split(q_pe),
                                      positions.reshape(n, cq)))
    out = out.swapaxes(0, 1).reshape(B, S, H * dv)
    y = common.dense(p["o"], out)
    if not return_cache:
        return y
    target = max(cache_len or S, S)
    rows = jnp.pad(rows, ((0, 0), (0, target - S), (0, 0)))
    return y, {"ckv": rows}


def absorbed_query(cfg: ArchConfig, p: dict, x, positions):
    """x (B, 1, D) at ``positions`` (B, 1) -> (B, H, R + dr): each head's
    query against the cached rows, ``[q_nope W_uk^T, q_pe]``."""
    q_nope, q_pe = _queries(cfg, p, x, positions)
    w_uk, _ = _kv_b(cfg, p)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    return jnp.concatenate([q_lat, q_pe[:, 0].astype(q_lat.dtype)], -1)


def absorbed_out(cfg: ArchConfig, p: dict, o_lat):
    """Per-head weighted latents (B, H, R) -> y (B, 1, D)."""
    _, w_uv = _kv_b(cfg, p)
    out = jnp.einsum("bhr,rhv->bhv", o_lat.astype(w_uv.dtype), w_uv)
    return common.dense(p["o"], out.reshape(out.shape[0], 1, -1))


def init_cache(cfg: ArchConfig, batch: int, cache_len: int) -> dict:
    return {"ckv": jnp.zeros((batch, cache_len, row_width(cfg)),
                             common.dtype_of(cfg))}


def mla_decode(cfg: ArchConfig, p: dict, x, cache: dict, *, index):
    """One-token absorbed step against a dense row cache. x: (B, 1, D);
    index: scalar position. Returns (y, new cache)."""
    R = cfg.kv_lora_rank
    pos = jnp.full((x.shape[0], 1), index, jnp.int32)
    row = latent_rows(cfg, p, x, pos)                          # (B,1,W)
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], row.astype(cache["ckv"].dtype), (0, index, 0))
    q = absorbed_query(cfg, p, x, pos)                         # (B,H,W)
    sc = jnp.einsum("bhw,bsw->bhs", q, ckv,
                    preferred_element_type=jnp.float32) * sm_scale(cfg)
    valid = jnp.arange(ckv.shape[1]) <= index
    pr = jax.nn.softmax(jnp.where(valid, sc, NEG_INF), -1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv[..., :R].astype(jnp.float32))
    return absorbed_out(cfg, p, o_lat), {"ckv": ckv}
