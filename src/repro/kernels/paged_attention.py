"""Ragged paged-attention decode Pallas kernel with DMA double-buffering.

One query token per sequence attends over that sequence's KV pages in a
physical block-paged pool (``serve/kv.py`` + ``serve/paged.py``): pool
layout ``(L, n_pages, page_size, 2*Kv, hd)`` — every layer's pages stacked,
as the decode step's layer scan carries them — with K/V *head-interleaved*
along the fused head axis (``[k0, v0, k1, v1, ...]``, the tpu_commons
fused-KV layout — one DMA per page moves both halves).  A ``layer`` index
picks the layer in place: the kernel DMAs ``pool[layer, page]`` straight
out of the stacked pool, so no layer is ever sliced out (a copy of its
whole pool) before the call.  A 4-D pool ``(n_pages, page_size, 2*Kv,
hd)`` is one layer, at index 0.  The kernel grid
is one program per sequence; each program walks its block table (a
scalar-prefetch array, so page ids are known before the DMAs they index)
a *block* of pages at a time — one page for this kernel, the fewest whole
pages that give a contraction ``MIN_BLOCK_ROWS`` rows for the latent
kernel (``pages_per_block``, from the page's shape) — and keeps
``buffer_depth`` blocks in flight: blocks ``b+1 .. b+depth-1`` stream
HBM->VMEM, one DMA per page, while block ``b``'s scores fold into the
running online-softmax state — the paper's headroom-during-transfer
question at kernel granularity (how much attention compute hides behind
page fetches?).  The tail is ragged: positions past ``lengths[s]`` are
masked, pages past the last are never fetched, so sequences need not
fill their last page or block, and table rows are padded with a trash
page that is never read.  The kernels return the walk's float32 output.

``interpret=None`` resolves per backend exactly like ``kernels/quant.py``
(compiled Mosaic on TPU/GPU, interpreter on CPU, where the DMA semantics
are emulated and the kernel is validated against ``kernels/ref.py``).

``paged_attention_xla`` is the pure-XLA twin the serve path dispatches to
on backends without a compiled Pallas lowering: the same page walk as a
``lax.scan``, with ``buffer_depth`` becoming the number of pages gathered
per step — the same knob, the same schedule; amortized gather/dispatch
overhead instead of DMA/compute overlap, which is why the
``serve.paged_attention`` sweep can observe the depth axis on every
backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant import resolve_interpret

NEG_INF = -1e30


MIN_BLOCK_ROWS = 128         # rows a contraction should see: one MXU tile
MAX_BLOCK_BYTES = 1 << 20    # VMEM for one ring slot's block of pages


def pages_per_block(page_size, row_bytes, max_pages):
    """Pages one loop iteration of a walk moves and contracts, from the
    page's shape (``row_bytes``: one position's row of one layer): the
    fewest whole pages holding ``MIN_BLOCK_ROWS`` rows, at most the
    table's ``max_pages`` and as many as ``MAX_BLOCK_BYTES`` of VMEM
    holds, and at least one."""
    want = -(-MIN_BLOCK_ROWS // page_size)
    fit = MAX_BLOCK_BYTES // (page_size * row_bytes)
    return max(1, min(want, max_pages, fit))


def walk_pages(tables, lengths, layer, pool, buf, sem, *, rows, width,
               scores, values):
    """One program's walk over sequence ``program_id(0)``'s pages: the
    DMA ring, the ragged mask and the online softmax every paged decode
    kernel shares. ``buf`` is the ring, (depth, pages a block, page_size,
    *row): each iteration waits for one block's page copies, each page
    fetched by its own DMA from the block table, and contracts the block
    as one (block rows, *row) operand, as stored.
    ``scores(block)`` gives the block's (rows, block rows) float32
    scores, ``values(p, block)`` the (rows, width) weighted values for
    their float32 probabilities ``p``. Returns the (rows, width) float32
    attention output."""
    s = pl.program_id(0)
    length = lengths[s]
    depth, per_block, page_size = buf.shape[:3]
    block_rows = per_block * page_size
    n_pages = jax.lax.div(length + page_size - 1, page_size)
    n_blocks = jax.lax.div(n_pages + per_block - 1, per_block)

    def each_copy(b, slot, act):
        # pages past the sequence's last are never fetched: their rows
        # hold whatever the slot last held, cleared below before a sum
        for i in range(per_block):
            j = b * per_block + i

            @pl.when(j < n_pages)
            def _(i=i, j=j):
                act(pltpu.make_async_copy(
                    pool.at[layer[0], tables[s, j]], buf.at[slot, i],
                    sem.at[slot]))

    # warm-up: fill the buffer ring before the first wait
    for d in range(min(depth, -(-tables.shape[1] // per_block))):
        each_copy(d, d, lambda c: c.start())

    def body(b, carry):
        acc, m, l = carry
        slot = jax.lax.rem(b, depth)
        each_copy(b, slot, lambda c: c.wait())

        # the last block's rows past ``length`` (a ragged tail, pages
        # never fetched) are zeroed in place: a masked probability is 0,
        # and 0 x NaN would still poison the value sum
        @pl.when(b == n_blocks - 1)
        def _clear_tail():
            shape = buf.shape[1:]
            pos = (b * block_rows
                   + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * page_size
                   + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
            buf[slot] = jnp.where(pos < length, buf[slot],
                                  jnp.zeros((), buf.dtype))

        block = buf[slot].reshape((block_rows,) + buf.shape[3:])
        sc = scores(block)
        pos = b * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_rows), 1)
        mask = pos < length          # ragged tail: pad positions masked
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, -1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
        onew = values(p, block)
        # refill this slot only after block b's compute consumed it — with
        # depth >= 2 the other depth-1 slots' DMAs are already in flight
        # behind this compute, which is the overlap the sweep measures
        @pl.when(b + depth < n_blocks)
        def _next():
            each_copy(b + depth, slot, lambda c: c.start())
        return acc * alpha + onew, m_new, l_new

    acc0 = jnp.zeros((rows, width), jnp.float32)
    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    return acc / jnp.maximum(l, 1e-30)


def paged_call(kern, q, pool, tables, lengths, layer, *, per_block,
               buffer_depth, width, name, interpret):
    """``kern`` over a grid of one program per sequence: the block
    tables, lengths and layer prefetched as scalars, ``q``'s row and the
    (S, H, ``width``) float32 output blocked per sequence, the pool left
    in HBM for the kernel's DMAs into a ring of ``buffer_depth`` blocks
    of ``per_block`` pages (the depth clamped to [1, the table's
    blocks]). ``name`` names the call, so that a profile finds the
    kernel by it whatever program calls it."""
    S, H = q.shape[:2]
    page = pool.shape[2:]
    depth = max(1, min(buffer_depth, -(-tables.shape[1] // per_block)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((None,) + q.shape[1:],
                               lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],   # pool stays HBM
        out_specs=pl.BlockSpec((None, H, width), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((depth, per_block) + page, pool.dtype),
                        pltpu.SemaphoreType.DMA((depth,))],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, width), jnp.float32),
        interpret=interpret, name=name,
    )(tables, lengths, layer.reshape(1), q, pool)


def _decode_kernel(tables, lengths, layer, q_ref, pool, o_ref, buf, sem, *,
                   n_kv, rep, sm_scale):
    H, hd = q_ref.shape
    qh = (q_ref[...].astype(jnp.float32) * sm_scale).reshape(n_kv, rep, hd)

    # the block goes float32 before K and V are split off its fused head
    # axis: split as packed bf16 rows it cost more than bf16 score dots
    # saved (7-10% a call on a TPU v5e at olmo-1b's and nemo's shapes)
    def kv(block, half):
        return block.astype(jnp.float32).reshape(
            block.shape[0], n_kv, 2, hd)[:, :, half, :]

    def scores(block):
        k = kv(block, 0)
        return jnp.concatenate(
            [jax.lax.dot_general(qh[g], k[:, g], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             for g in range(n_kv)], axis=0)                   # (H, rows)

    def values(p, block):
        v, ph = kv(block, 1), p.reshape(n_kv, rep, block.shape[0])
        return jnp.concatenate(
            [jax.lax.dot_general(ph[g], v[:, g], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             for g in range(n_kv)], axis=0)                   # (H, hd)

    o_ref[...] = walk_pages(tables, lengths, layer, pool, buf, sem, rows=H,
                            width=hd, scores=scores, values=values)


def _stacked(pool, layer, rank=4):
    """A one-layer pool (``rank`` dims: 4 for K/V pages) as the one layer
    of a stacked pool (a free reshape), and ``layer`` as an int32 scalar:
    traced, so one program serves every layer."""
    if pool.ndim == rank:
        pool = pool[None]
    return pool, jnp.asarray(layer, jnp.int32)


def paged_attention_fwd(q, pool, tables, lengths, layer=0, *, buffer_depth=2,
                        sm_scale=None, interpret=None):
    """q: (S, H, hd) one decode token per sequence;
    pool: (L, n_pages, page_size, 2*Kv, hd) head-interleaved K/V pages of
    L layers, or (n_pages, page_size, 2*Kv, hd) for one;
    tables: (S, max_pages) int32 page ids (trash-padded past each
    sequence's reserved pages); lengths: (S,) valid tokens per sequence;
    layer: the layer of ``pool`` to attend (int or traced int32 scalar;
    0 for a 4-D pool).  The kernel reads the pages of that layer where
    they lie.  Returns (S, H, hd) float32.  ``buffer_depth`` is the
    number of pages kept in flight (static; ``paged_call``)."""
    interpret = resolve_interpret(interpret)
    pool, layer = _stacked(pool, layer)
    _, H, hd = q.shape
    kv2 = pool.shape[3]
    n_kv = kv2 // 2
    rep = H // n_kv
    assert n_kv * rep == H, (H, n_kv)
    sm_scale = sm_scale if sm_scale is not None else hd ** -0.5
    kern = functools.partial(_decode_kernel, n_kv=n_kv, rep=rep,
                             sm_scale=sm_scale)
    # a page a block: this kernel is bound per row (K and V split off the
    # fused head axis), not by its loop, and a block is contracted whole,
    # so an idle slot would attend a block of masked rows. 8-page blocks
    # cost mistral-nemo's decode cell 5.5% of its tokens/s and olmo-1b's
    # chat step 27% on a TPU v5e
    return paged_call(kern, q, pool, tables, lengths, layer, per_block=1,
                      buffer_depth=buffer_depth, width=hd,
                      name="paged_attention", interpret=interpret)


def walk_pages_xla(pool, tables, lengths, layer, *, buffer_depth, scores,
                   values, out_shape):
    """The XLA twin of ``walk_pages`` for every sequence at once: a scan
    over the block tables in chunks of ``buffer_depth`` pages, gathered
    together and folded into the same online softmax. ``scores(chunk)``
    gives (S, ..., T) scores of a float32 chunk (S, T, *page row), with T
    its positions; ``values(p, chunk)`` the weighted values, shaped as
    ``out_shape`` (S, ..., width). Returns the float32 output."""
    S, max_pages = tables.shape
    _, n_pages_tot, page_size = pool.shape[:3]
    depth = max(1, min(buffer_depth, max_pages))
    n_chunks = -(-max_pages // depth)
    pad = n_chunks * depth - max_pages
    # pad ragged chunk tails with the trash page (id n_pages_tot - 1 by
    # construction, serve/paged.py) — masked below, never contributes
    tbl = jnp.pad(tables, ((0, 0), (0, pad)), constant_values=n_pages_tot - 1)
    tbl = tbl.reshape(S, n_chunks, depth).swapaxes(0, 1)    # (C, S, depth)
    pos = (jnp.arange(n_chunks * depth)[:, None] * page_size
           + jnp.arange(page_size)[None]).reshape(n_chunks, depth * page_size)
    lead = (S,) + (1,) * (len(out_shape) - 2)

    def body(carry, inp):
        acc, m, l = carry
        tbl_c, pos_c = inp
        chunk = pool[layer, tbl_c].astype(jnp.float32).reshape(
            (S, depth * page_size) + pool.shape[3:])
        sc = scores(chunk)
        mask = (pos_c[None] < lengths[:, None]).reshape(lead + (-1,))
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, -1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] + values(p, chunk)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros(out_shape, jnp.float32)
    m0 = jnp.full(out_shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(out_shape[:-1], jnp.float32)
    (acc, _, l), _ = jax.lax.scan(body, (acc0, m0, l0), (tbl, pos))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def paged_attention_xla(q, pool, tables, lengths, layer=0, *, buffer_depth=2,
                        sm_scale=None):
    """Pure-XLA twin of the kernel (``walk_pages_xla``): scan over the
    block table in chunks of ``buffer_depth`` pages.  Identical math and
    walk order; the depth knob here amortizes per-page gather/dispatch
    overhead rather than overlapping DMA, so the page-size x depth sweep
    stays observable on CPU.  The pool and ``layer`` as for the kernel:
    each chunk gathers ``pool[layer, pages]``."""
    pool, layer = _stacked(pool, layer)
    S, H, hd = q.shape
    n_kv = pool.shape[3] // 2
    rep = H // n_kv
    sm_scale = sm_scale if sm_scale is not None else hd ** -0.5
    qh = q.reshape(S, n_kv, rep, hd).astype(jnp.float32) * sm_scale

    def kv(chunk, half):
        return chunk.reshape(chunk.shape[:2] + (n_kv, 2, hd))[..., half, :]

    out = walk_pages_xla(
        pool, tables, lengths, layer, buffer_depth=buffer_depth,
        scores=lambda c: jnp.einsum("sgrh,stgh->sgrt", qh, kv(c, 0)),
        values=lambda p, c: jnp.einsum("sgrt,stgh->sgrh", p, kv(c, 1)),
        out_shape=(S, n_kv, rep, hd))
    return out.reshape(S, H, hd).astype(q.dtype)
