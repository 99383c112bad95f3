"""Ragged paged latent-attention (MLA) decode Pallas kernel.

Latent attention caches one row per token and layer: the normed latent
``c`` (``kv_lora_rank`` wide) and the rotary key ``k_pe`` shared by every
head, side by side — a ``(L, n_pages, page_size, W)`` pool, the row
(``kv_lora_rank + qk_rope_head_dim``, 576 at DeepSeek-V3 widths)
zero-padded to ``W``, a whole number of 128-lane tiles (640): Mosaic
moves only whole tiles of the minor dimension. The decode step absorbs
the key expansion into the query (``models/mla.py``), so each head's
query is one ``W``-wide row ``[q_nope W_uk^T, q_pe, 0…]``: its score
against a position is ``q · row`` and its output the softmax-weighted
sum of the rows' first ``kv_lora_rank`` columns — the value is a prefix
of the key. One page of rows serves all heads at once.

The page walk (grid, block tables, DMA ring, ragged mask, online
softmax) is ``kernels/paged_attention``'s ``walk_pages``, with the score
and value contractions of a latent row: one program per sequence walks
its table a block of pages (at least 128 rows) at a time, with
``buffer_depth`` blocks in flight, straight out of the stacked pool at a
traced ``layer`` index. The roofline share counts the row's 576
columns, so the padding shows as lost share.

``paged_mla_attention_xla`` is the XLA twin, on ``walk_pages_xla``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import (_stacked, pages_per_block,
                                           paged_call, walk_pages,
                                           walk_pages_xla)
from repro.kernels.quant import resolve_interpret


def _mla_kernel(tables, lengths, layer, q_ref, pool, o_ref, buf, sem, *,
                latent, sm_scale):
    # bf16 queries meet bf16 rows as stored (their products are exact in
    # the float32 accumulator); a float32 side keeps the dot float32
    dt = jnp.promote_types(q_ref.dtype, pool.dtype)
    q = q_ref[...].astype(dt)                                  # (H, W)

    def scores(rows):                                          # (n, W)
        return jax.lax.dot_general(q, rows.astype(dt),
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32
                                   ) * sm_scale

    def values(p, rows):
        return jax.lax.dot_general(p, rows[:, :latent].astype(jnp.float32),
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    o_ref[...] = walk_pages(
        tables, lengths, layer, pool, buf, sem, rows=q_ref.shape[0],
        width=latent, scores=scores, values=values)


def paged_mla_attention_fwd(q, pool, tables, lengths, layer=0, *, latent,
                            sm_scale, buffer_depth=2, interpret=None):
    """q: (S, H, W) absorbed queries, one decode token per sequence;
    pool: (L, n_pages, page_size, W) latent rows of L layers, or (n_pages,
    page_size, W) for one; tables: (S, max_pages) int32 page ids;
    lengths: (S,) valid positions per sequence; layer: the layer of
    ``pool`` to attend; latent: the leading columns of a row that are its
    value. Returns (S, H, latent) float32."""
    interpret = resolve_interpret(interpret)
    pool, layer = _stacked(pool, layer, rank=3)
    assert pool.shape[3] == q.shape[2], (pool.shape, q.shape)
    kern = functools.partial(_mla_kernel, latent=latent, sm_scale=sm_scale)
    per_block = pages_per_block(pool.shape[2],
                                pool.shape[3] * pool.dtype.itemsize,
                                tables.shape[1])
    return paged_call(kern, q, pool, tables, lengths, layer,
                      per_block=per_block, buffer_depth=buffer_depth,
                      width=latent, name="paged_mla_attention",
                      interpret=interpret)


def paged_mla_attention_xla(q, pool, tables, lengths, layer=0, *, latent,
                            sm_scale, buffer_depth=2):
    """XLA twin of the kernel: the same page walk and online softmax, a
    chunk of ``buffer_depth`` pages gathered per scan step."""
    pool, layer = _stacked(pool, layer, rank=3)
    S, H, _ = q.shape
    qs = q.astype(jnp.float32) * sm_scale
    out = walk_pages_xla(
        pool, tables, lengths, layer, buffer_depth=buffer_depth,
        scores=lambda rows: jnp.einsum("shw,stw->sht", qs, rows),
        values=lambda p, rows: jnp.einsum("sht,str->shr", p,
                                          rows[..., :latent]),
        out_shape=(S, H, latent))
    return out.astype(q.dtype)
