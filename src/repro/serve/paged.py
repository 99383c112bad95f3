"""Physical block-paged KV: pool tensors, page insertion, paged decode.

Per-slot caches would stack a full ``cache_len`` KV cache per slot; the
page pool is ONE preallocated tensor per layer of the scanned group,
stacked over the group's layers — shape ``(G, n_pages, block_size,
2*Kv, hd)`` (layer, then pages; the kernel reads it at a layer index) —
with K/V *head-interleaved* on the fused head axis (``[k0, v0, k1, v1,
...]``): a page is the unit of both allocation (``serve/kv.py`` block
ids ARE page ids) and data movement (one DMA per page moves keys and
values together).  Requests own pages through the
allocator's block tables; the device sees fixed-width table rows padded
with the trash page (id ``n_blocks``), so the decode step's shapes never
depend on how many pages a request holds.

Three jit-able pieces (wired into cells by ``serve/step.py``):

* ``init_kv_pool`` — the pool pytree (zeros; one leaf per layer-in-group,
  all layers share one block table since every layer caches the same
  positions).
* ``insert_pages`` — admission: scatter a batch-1 prefill cache into the
  request's pages, one ``dynamic_update_slice`` per page (pages past the
  reservation land on the trash page, harmlessly).
* ``paged_decode_step`` — the batched decode step over all slots: project
  q/k/v per slot, write each slot's new token into its current page
  (``dynamic_update_slice`` at ``(g, table[idx // bs], idx % bs)`` of the
  stacked leaf), then attend over the block table via
  ``kernels/ops.paged_attention`` — the ragged paged-attention kernel (or
  its XLA twin) walking layer ``g``'s pages with ``buffer_depth`` blocks
  of pages in flight.  The pool rides the layer scan's *carry* (not its scanned
  inputs and outputs), and the kernel is handed the whole stacked leaf
  with the layer index: the writes alias the donated pool and the reads
  address it in place, so a step moves the bytes it attends and its 16
  token rows, never a copy of the pool.  Non-attention sublayers (norms,
  MLP/MoE, residuals, logits) reuse the exact ``models/transformer`` code;
  served tokens are held to the float32 references of ``perfbench/``
  and stay bit-identical across tp=1/2/4 at f32 (differential-tested).

The pool's leaves follow the state kind (``state_kind``): K/V pages as
above, or, for latent attention (``kv_lora_rank > 0``), *latent pages*
``(G, n_pages, block_size, W)`` — one row per token and layer, the normed
latent and the shared rope key (``kv_lora_rank + qk_rope_head_dim``, 576
at DeepSeek-V3 widths) zero-padded to ``W``, the next multiple of the
chip's 128 lanes (640): a page's DMA then moves whole lane tiles, which
Mosaic requires, and the padding costs no HBM, since the tiled layout
pads a 576-wide row to 640 lanes anyway.  The rows are written by the
absorbed decode (``models/mla.py``) and attended in place by
``kernels/ops.paged_mla_attention``.
Leading dense layers (``first_k_dense_replace``) keep their pages in a
``"dense"`` leaf, walked before the scan.  An MoE decode step also
returns, per MoE layer, the tokens routed to each expert (``load``, a
third output only a config with experts has): the engine fetches it with
the sampled tokens.  A slot whose table row starts at the trash page
holds no request: its token is routed to no expert, so free slots cost
the expert layer nothing and ``load`` counts live tokens only.

The pool holds all-attention families with full (non-windowed)
attention (``paged_supported``) — the architectures where a physical
page pool buys long context and oversubscription; ``serve/step.make_cells``
serves every such config from the pool and keeps SSM/hybrid/SWA states
in per-slot caches.  Latent attention runs on one device (``tp_size`` 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import common, mla, moe, transformer
from repro.parallel import compat, sharding


def paged_supported(cfg: ArchConfig) -> bool:
    """Every layer an attention layer, no sliding window."""
    return (cfg.family != "ssm" and cfg.sliding_window == 0
            and all(cfg.is_attn_layer(i) for i in range(cfg.layer_group)))


def check_paged(cfg: ArchConfig, cache_len: int, block_size: int,
                tp_size: int = 1) -> None:
    """Refuse what the pool cannot serve for a pageable ``cfg``."""
    if cfg.mla and tp_size > 1:
        raise ValueError(
            f"paged latent attention runs on one device: {cfg.name} has "
            f"kv_lora_rank={cfg.kv_lora_rank}, asked for tp_size={tp_size} "
            f"(tensor parallelism for latent attention is not supported)")
    if cache_len % block_size:
        raise ValueError(
            f"paged KV needs cache_len divisible by block_size "
            f"({cache_len} % {block_size} != 0): pages tile the cache")


def fuse_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """Interleave K/V along the head axis: (..., Kv, hd) x2 ->
    (..., 2*Kv, hd) ordered [k0, v0, k1, v1, ...]."""
    stacked = jnp.stack([k, v], axis=-2)        # (..., Kv, 2, hd)
    return stacked.reshape(stacked.shape[:-3]
                           + (2 * k.shape[-2], k.shape[-1]))


def state_kind(cfg: ArchConfig) -> str:
    """What a page holds: ``"latent"`` rows (MLA) or ``"kv"`` pairs."""
    return "latent" if cfg.mla else "kv"


LANES = 128


def latent_width(cfg: ArchConfig) -> int:
    """A latent page row's width: the row, padded to whole lane tiles."""
    return -(-mla.row_width(cfg) // LANES) * LANES


def _pad_row(x, width: int):
    """Zero-pad the last axis of ``x`` to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _row_dims(cfg: ArchConfig) -> tuple:
    """A cached token's dims in one layer's pages."""
    if cfg.mla:
        return (latent_width(cfg),)
    return (2 * cfg.num_kv_heads, cfg.hd)


def init_kv_pool(cfg: ArchConfig, n_pages: int, block_size: int):
    """Zeroed pool pytree: ``{"l{i}": (G, n_pages, bs, *row)}``, plus
    ``"dense"`` ``(first_k_dense_replace, n_pages, bs, *row)``; a row is
    ``(2*Kv, hd)`` K/V or ``(kv_lora_rank + qk_rope_head_dim,)``."""
    def leaf(n_layers):
        return jnp.zeros((n_layers, n_pages, block_size) + _row_dims(cfg),
                         common.dtype_of(cfg))
    pool = {f"l{i}": leaf(cfg.num_groups()) for i in range(cfg.layer_group)}
    if cfg.first_k_dense_replace:
        pool["dense"] = leaf(cfg.first_k_dense_replace)
    return pool


def pool_geometry(cfg: ArchConfig, n_pages: int, block_size: int) -> dict:
    """Physical footprint of the pool ``init_kv_pool`` materializes, for
    the tracer's pool-geometry instant and Record params: page count,
    bytes per page across every leaf, total pool bytes, and what a page
    holds."""
    import numpy as np
    itemsize = np.dtype(common.dtype_of(cfg)).itemsize
    page_bytes = (cfg.num_layers * block_size * int(np.prod(_row_dims(cfg)))
                  * itemsize)
    return {"n_pages": n_pages, "block_size": block_size,
            "page_bytes": page_bytes, "pool_bytes": page_bytes * n_pages,
            "state_kind": state_kind(cfg)}


def _constrain_pool(pool_l):
    """Pool split over 'model' on the fused head axis (pruned by
    ``safe_spec`` when 2*Kv is not divisible); pages/positions local."""
    return sharding.constrain(pool_l, *([None] * (pool_l.ndim - 2)),
                              "heads", None)


def insert_pages(cfg: ArchConfig, pool, base_caches, table_row):
    """Scatter a batch-1 prefill cache into the pages of ``table_row``.

    ``base_caches``: the prefill cell's output (``{"l{i}": {"k": (G, 1,
    cache_len, Kv, hd), ...}}``); ``table_row``: (max_pages,) int32 page
    ids, trash-padded.  One ``dynamic_update_slice`` per page per layer —
    the whole row is written (a fresh admission overwrites any stale page
    content; writes past the reservation land on the trash page).
    """
    bs = next(iter(pool.values())).shape[2]
    new_pool = {}
    for key, pool_l in pool.items():
        cache = base_caches[key]
        if cfg.mla:
            rows = _pad_row(cache["ckv"][:, 0], pool_l.shape[-1])  # (G,S,W)
        else:
            rows = fuse_kv(cache["k"][:, 0], cache["v"][:, 0])  # (G,S,2Kv,hd)
        rows = rows.astype(pool_l.dtype)
        max_pages = rows.shape[1] // bs
        assert table_row.shape[0] >= max_pages, \
            (table_row.shape, max_pages)
        zeros = (0,) * (rows.ndim - 2)
        for j in range(max_pages):
            page = rows[:, None, j * bs:(j + 1) * bs]    # (G,1,bs,*row)
            pool_l = jax.lax.dynamic_update_slice(
                pool_l, page, (0, table_row[j], 0) + zeros)
        new_pool[key] = _constrain_pool(pool_l)
    return new_pool


# ---------------------------------------------------------------------------
# paged decode step
# ---------------------------------------------------------------------------

def _attend(cfg: ArchConfig, q, pool_l, g, tables, lengths, *,
            buffer_depth):
    """``kernels/ops.paged_attention`` over layer ``g`` of the stacked
    leaf ``pool_l``.

    Under a mesh it runs once per 'model' shard inside ``shard_map``:
    heads are independent, and the compiler cannot partition a Mosaic
    kernel itself.  A shard holds whole K/V pairs and the query heads
    that read them when ``num_kv_heads`` divides over 'model'; otherwise
    every shard attends over all heads.  The layer index is replicated.
    """
    from repro.kernels import ops as kops
    attend = functools.partial(kops.paged_attention,
                               buffer_depth=buffer_depth)
    ctx = sharding.get_ctx()
    if ctx is None or not ctx.enabled:
        return attend(q, pool_l, tables, lengths, g)
    heads = ctx.mesh_axes("heads")
    if not heads or cfg.num_kv_heads % ctx.axis_size("heads"):
        heads = None
    q_spec = P(None, heads, None)
    pool_spec = P(None, None, None, heads, None)
    return compat.shard_map(attend, ctx.mesh,
                            in_specs=(q_spec, pool_spec, P(), P(), P()),
                            out_specs=q_spec)(q, pool_l, tables, lengths, g)


def _paged_attn_decode(cfg: ArchConfig, p: dict, x, pool_l, g, idx, tables,
                       *, buffer_depth):
    """Batched one-token paged attention for layer ``g`` of the group.

    x: (S, 1, D) normed activations for every slot; pool_l: (G, n_pages,
    bs, 2*Kv, hd) — the whole stacked leaf, carried by the caller's scan;
    g: the traced layer index into it; idx: (S,) per-slot positions;
    tables: (S, max_pages).  Returns (y (S,1,D), updated pool_l).
    Mirrors ``models/attention.attn_decode`` exactly (projection, rope at
    ``idx``, write-then-attend, output projection) with the cache swapped
    for pool pages.
    """
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    S = x.shape[0]
    bs = pool_l.shape[2]

    q = common.dense(p["q"], x).reshape(S, 1, H, hd)
    k = common.dense(p["k"], x).reshape(S, 1, Kv, hd)
    v = common.dense(p["v"], x).reshape(S, 1, Kv, hd)
    pos = idx[:, None].astype(jnp.int32)                 # (S, 1)
    q = common.apply_rope(q, pos, cfg.rope_theta)
    k = common.apply_rope(k, pos, cfg.rope_theta)

    # write each slot's new token into its current page of layer g — the
    # paged form of attn_decode's cache dynamic_update_slice (free
    # slots write the trash page: their tables are all-trash, reads stay
    # length-masked)
    fused = fuse_kv(k[:, 0], v[:, 0]).astype(pool_l.dtype)   # (S, 2Kv, hd)
    for s in range(S):
        page, off = tables[s, idx[s] // bs], idx[s] % bs
        pool_l = jax.lax.dynamic_update_slice(
            pool_l, fused[s][None, None, None], (g, page, off, 0, 0))
    pool_l = _constrain_pool(pool_l)

    out = _attend(cfg, q[:, 0], pool_l, g, tables, idx + 1,
                  buffer_depth=buffer_depth)                 # (S, H, hd)
    out = out.reshape(S, 1, H * hd)
    y = common.dense(p["o"], out)
    return y, pool_l


def _paged_mla_decode(cfg: ArchConfig, p: dict, x, pool_l, g, idx, tables,
                      *, buffer_depth):
    """Batched one-token latent attention for layer ``g``: the absorbed
    form of ``models/mla.mla_decode`` with the row cache swapped for the
    latent pages of ``pool_l`` (G, n_pages, bs, W), written then attended
    in place like the K/V path."""
    from repro.kernels import ops as kops
    S = x.shape[0]
    bs, W = pool_l.shape[2], pool_l.shape[-1]
    pos = idx[:, None].astype(jnp.int32)                 # (S, 1)
    rows = _pad_row(mla.latent_rows(cfg, p, x, pos)[:, 0], W)
    rows = rows.astype(pool_l.dtype)
    for s in range(S):
        page, off = tables[s, idx[s] // bs], idx[s] % bs
        pool_l = jax.lax.dynamic_update_slice(
            pool_l, rows[s][None, None, None], (g, page, off, 0))
    q = _pad_row(mla.absorbed_query(cfg, p, x, pos), W)  # (S, H, W)
    o_lat = kops.paged_mla_attention(
        q, pool_l, tables, idx + 1, g, latent=cfg.kv_lora_rank,
        sm_scale=mla.sm_scale(cfg), buffer_depth=buffer_depth)
    return mla.absorbed_out(cfg, p, o_lat), pool_l


def _paged_layer_decode(cfg: ArchConfig, p: dict, x, pool_l, g, idx, tables,
                        *, buffer_depth, live, experts=None):
    """``transformer._layer_decode`` with paged attention. Returns (x,
    pool_l, load): the tokens of ``live`` slots routed to each expert, or
    None for a layer without experts.  ``experts``: the routed experts'
    kernels stacked over the scan (``moe.split_experts``), read at
    ``g``."""
    h = common.norm_apply(cfg, p["norm1"], x)
    attend = _paged_mla_decode if cfg.mla else _paged_attn_decode
    y, pool_l = attend(cfg, p["attn"], h, pool_l, g, idx, tables,
                       buffer_depth=buffer_depth)
    if cfg.parallel_block:
        f, _ = transformer._ffn(cfg, p, h, serving=True)
        return x + y + f, pool_l, None
    x = x + y
    h2 = common.norm_apply(cfg, p["norm2"], x)
    if "moe" in p:
        f, load = moe.moe_serve(cfg, dict(p["moe"], **experts), h2,
                                live=live, layer=g)
    else:
        (f, _), load = transformer._ffn(cfg, p, h2, serving=True), None
    return x + f, pool_l, load


def paged_decode_step(cfg: ArchConfig, params: dict, tokens, idx, pool,
                      tables, *, buffer_depth=2):
    """One decode step for every slot against the paged pool.

    tokens: (S, 1) int32; idx: (S,) per-slot positions; pool: the
    ``init_kv_pool`` pytree; tables: (S, max_pages) int32.  Returns
    (logits (S, 1, V) fp32, updated pool), and with routed experts a third
    output, ``load`` int32: the live slots' tokens routed to each expert,
    (G, E) for one MoE layer a group, (G, n, E) for n.

    The scan runs over ``(params["layers"], arange(G))`` and carries the
    pool: were the pool a scanned input and output, XLA would slice each
    layer out of it, copy the donated input into a fresh stacked output
    and write each layer back — three passes over the whole pool a step.
    """
    x = params["embed"]["embedding"][tokens]             # (S, 1, D)
    trash = next(iter(pool.values())).shape[1] - 1
    live = tables[:, 0] != trash
    pool = dict(pool)
    for j in range(cfg.first_k_dense_replace):
        x, pool["dense"], _ = _paged_layer_decode(
            cfg, transformer.dense_layer(params["dense_layers"], j), x,
            pool["dense"], j, idx, tables, buffer_depth=buffer_depth,
            live=live)

    layers, held = moe.split_experts(params["layers"])

    def body(carry, inp):
        x, pool = carry
        gp, g = inp
        pool = dict(pool)
        loads = []
        for i in range(cfg.layer_group):
            x, pool[f"l{i}"], load = _paged_layer_decode(
                cfg, gp[f"l{i}"], x, pool[f"l{i}"], g, idx, tables,
                buffer_depth=buffer_depth, live=live,
                experts=held.get(f"l{i}"))
            if load is not None:
                loads.append(load)
        if len(loads) > 1:
            loads = [jnp.stack(loads)]
        return (x, pool), (loads[0] if loads else None)

    (x, new_pool), load = jax.lax.scan(
        body, (x, pool), (layers, jnp.arange(cfg.num_groups())))
    x = common.norm_apply(cfg, params["final_norm"], x)
    logits = transformer._logits(cfg, params, x)
    return (logits, new_pool) if load is None else (logits, new_pool, load)
