"""Grouped matmul over rows sorted by expert: the routed experts' FFN.

``lhs`` (m, k) holds token rows sorted by the expert they were routed to,
``group_sizes`` (E,) how many rows each expert owns, in order; ``rhs``
(E, k, n) the experts' weights, or (L, E, k, n) every layer's with a
``layer`` index (a scalar-prefetch operand, traced: a layer scan hands
the kernel the stacked weights and reads its layer in place, where a
sliced-out layer would first be copied whole). Row ``i`` of the result
is ``lhs[i] @ rhs[layer, e]`` for the expert ``e`` that owns it. Rows
past ``sum(group_sizes)`` belong to no expert and are left unwritten:
the caller masks them.

The kernel follows the megablox ``gmm`` of ``jax.experimental.pallas.ops.
tpu.megablox``: the grid walks (n tile, visit), where a visit is one
(expert, m tile) pair whose rows meet. Visits come in row order, so an
m tile is revisited only consecutively and its output block stays in
VMEM while each expert that owns part of it writes its rows (masked). An
expert's weight block is fetched when the visit's expert changes: every
expert that holds a row is streamed once per n tile, an expert that
holds none not at all. The number of visits is computed on the device
from ``group_sizes`` and sizes the grid, so the kernel's time follows the
experts hit, not the experts held. The contraction is whole (one k
tile), and the n tile is the widest whose weight block fits 8 MiB.

Unlike the megablox kernel it is named (``pallas_call(name=
"expert_gmm")``), so a profile shows it as ``%expert_gmm…``; it has no
k remainder, sharding offset, transpose or existing output, which the
serving path does not use. ``expert_gmm_xla`` is its XLA twin, which
backends without a compiled Pallas lowering take
(``kernels/ops.expert_gmm``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant import resolve_interpret

# largest weight block (k x tn) one visit streams; two are in flight
RHS_BLOCK_BYTES = 8 << 20
VMEM_LIMIT_BYTES = 64 << 20


def row_tile(m: int) -> int:
    """m-tile: 16 rows for a decode step's few rows per expert (the
    bfloat16 sublane tile), 128 for a prefill's many."""
    return 128 if m >= 1024 else 16


def col_tile(k: int, n: int, itemsize: int) -> int:
    """The widest n tile (n itself, or n halved while a multiple of 128)
    whose (k, tn) weight block fits ``RHS_BLOCK_BYTES``."""
    tn = n
    while k * tn * itemsize > RHS_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def group_metadata(group_sizes, m: int, tm: int):
    """(group_offsets (E+1,), group_ids, m_tile_ids) per visit, and the
    number of visits: the megablox ``make_group_metadata`` without
    sharding or empty-group visits. Visits are ordered by expert, then
    m tile; ``tiles_m + E - 1`` bounds their count."""
    E = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    # tiles an expert touches: its rows, rounded out to whole tiles
    touched = jnp.where(group_sizes == 0, 0,
                        (ends + tm - 1) // tm - starts // tm)
    group_ids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), touched,
                           total_repeat_length=tiles_m + E - 1)
    # each tile is visited once, plus once per expert that starts inside
    # it (not on its first row)
    partial = jnp.where((starts % tm == 0) | (group_sizes == 0), tiles_m,
                        starts // tm)
    visits = 1 + jnp.zeros(tiles_m, jnp.int32).at[partial].add(
        1, mode="drop")
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=tiles_m + E - 1)
    return (offsets, group_ids, m_tile_ids), jnp.sum(touched)


def _expert_gmm_kernel(offsets, group_ids, m_tile_ids, layer, lhs, rhs, out,
                       *, tm: int):
    v = pl.program_id(1)
    e = group_ids[v]
    row = m_tile_ids[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, out.shape, 0)
    mine = (row >= offsets[e]) & (row < offsets[e + 1])
    acc = jax.lax.dot_general(lhs[...], rhs[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    out[...] = jnp.where(mine, acc, out[...].astype(jnp.float32)).astype(
        out.dtype)


def _stacked(rhs, layer):
    """(L, E, k, n) weights and an int32 ``layer`` of shape (1,)."""
    if rhs.ndim == 3:
        rhs = rhs[None]
    return rhs, jnp.asarray(0 if layer is None else layer,
                            jnp.int32).reshape(1)


def expert_gmm_fwd(lhs, rhs, group_sizes, layer=None, *,
                   out_dtype=jnp.float32, interpret=None):
    """``lhs`` (m, k), ``rhs`` (E, k, n) or (L, E, k, n) with ``layer``,
    ``group_sizes`` (E,) int32 -> (m, n) in ``out_dtype``; rows past
    ``sum(group_sizes)`` unwritten."""
    interpret = resolve_interpret(interpret)
    rhs, layer = _stacked(rhs, layer)
    m, k = lhs.shape
    _, E, k2, n = rhs.shape
    assert k == k2, (lhs.shape, rhs.shape)
    tm = row_tile(m)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    mp = m + pad
    tn = col_tile(k, n, rhs.dtype.itemsize)
    meta, n_visits = group_metadata(group_sizes.astype(jnp.int32), mp, tm)

    def lhs_map(n_i, v, offsets, group_ids, m_tile_ids, layer):
        return m_tile_ids[v], 0

    def rhs_map(n_i, v, offsets, group_ids, m_tile_ids, layer):
        return layer[0], group_ids[v], 0, n_i

    def out_map(n_i, v, offsets, group_ids, m_tile_ids, layer):
        return m_tile_ids[v], n_i

    out = pl.pallas_call(
        functools.partial(_expert_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, k), lhs_map),
                      pl.BlockSpec((None, None, k, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="expert_gmm",
    )(*meta, layer, lhs, rhs)
    return out[:m] if pad else out


def expert_gmm_xla(lhs, rhs, group_sizes, layer=None, *,
                   out_dtype=jnp.float32):
    """The XLA twin: every row through every expert, each row keeping its
    own expert's product (rows past the groups read 0) — E times the
    kernel's operations, for backends that run the model at test sizes.
    Plain einsums, where ``lax.ragged_dot`` has no batching rule for a
    decode step mapped over slots (the per-slot caches' decode)."""
    rhs, layer = _stacked(rhs, layer)
    E = rhs.shape[1]
    expert = jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(
        lhs.shape[0]), side="right")
    every = jnp.einsum("mk,ekn->emn", lhs, rhs[layer[0]],
                       preferred_element_type=out_dtype)
    own = jax.nn.one_hot(expert, E, dtype=out_dtype)          # 0 past E
    return jnp.einsum("emn,me->mn", every, own)
