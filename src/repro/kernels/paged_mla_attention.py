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
its table with ``buffer_depth`` page copies in flight, straight out of
the stacked pool at a traced ``layer`` index. The roofline share counts
the row's 576 columns, so the padding shows as lost share.

``paged_mla_attention_xla`` is the XLA twin, on ``walk_pages_xla``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import (_stacked, paged_call, walk_pages,
                                           walk_pages_xla)
from repro.kernels.quant import resolve_interpret


def _mla_kernel(tables, lengths, layer, q_ref, pool, o_ref, buf, sem, *,
                page_size, depth, max_pages, latent, sm_scale):
    q = q_ref[...].astype(jnp.float32) * sm_scale              # (H, W)

    def scores(rows):                                          # (ps, W)
        return jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def values(p, rows):
        return jax.lax.dot_general(p, rows[:, :latent],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    o_ref[...] = walk_pages(
        tables, lengths, layer, pool, buf, sem, page_size=page_size,
        depth=depth, max_pages=max_pages, rows=q_ref.shape[0], width=latent,
        scores=scores, values=values).astype(o_ref.dtype)


def paged_mla_attention_fwd(q, pool, tables, lengths, layer=0, *, latent,
                            sm_scale, buffer_depth=2, interpret=None):
    """q: (S, H, W) absorbed queries, one decode token per sequence;
    pool: (L, n_pages, page_size, W) latent rows of L layers, or (n_pages,
    page_size, W) for one; tables: (S, max_pages) int32 page ids;
    lengths: (S,) valid positions per sequence; layer: the layer of
    ``pool`` to attend; latent: the leading columns of a row that are its
    value. Returns (S, H, latent)."""
    interpret = resolve_interpret(interpret)
    pool, layer = _stacked(pool, layer, rank=3)
    assert pool.shape[3] == q.shape[2], (pool.shape, q.shape)
    max_pages = tables.shape[1]
    depth = max(1, min(buffer_depth, max_pages))
    kern = functools.partial(
        _mla_kernel, page_size=pool.shape[2], depth=depth,
        max_pages=max_pages, latent=latent, sm_scale=sm_scale)
    return paged_call(kern, q, pool, tables, lengths, layer, depth=depth,
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
