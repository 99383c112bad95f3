"""int8 quantize/dequantize Pallas TPU kernels.

The compute hot-spot of the in-path gradient compression (the paper's
offloaded transform).  Rowwise symmetric scales; (rows, cols) tiles
stream through VMEM so the transform runs at HBM bandwidth.

``interpret=None`` (the default) resolves per backend: compiled Mosaic /
Triton on TPU and GPU, interpreter on CPU — keyed on
``jax.default_backend()``, never on the jax version.  Ragged row and
column counts are zero-padded up to the tile and the padding sliced off
the result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")

# Payload size (elements) above which the quantize/dequantize transform is
# worth a Pallas kernel launch — below it the launch overhead beats the
# saving (the paper's offload-profitability rule, applied to the transform
# itself).  ``kernels/ops.py`` keys the ``quant_impl="auto"`` policy on it.
PALLAS_QUANT_MIN_SIZE = 1 << 16


def resolve_interpret(interpret):
    """None -> auto: compiled where Pallas has a real lowering, interpreted
    on CPU — keyed on ``jax.default_backend()``, never the jax version.
    Explicit booleans pass through untouched."""
    if interpret is None:
        return jax.default_backend() not in _COMPILED_BACKENDS
    return interpret


# Bytes of one f32 input tile.  Mosaic's scoped VMEM is 16 MiB and the
# pipeline double-buffers every operand, so a (block_rows, C) tile of a
# long row (a 16 MiB bucket chunked over 4 devices is 4 rows of 1M) does
# not fit; such rows are split into column tiles of at most this size.
_TILE_BYTES = 2 << 20


def _pad_axis(x, axis, multiple):
    """Zero-pad ``axis`` up to a multiple of ``multiple``.  Returns
    (x, pad)."""
    pad = (-x.shape[axis]) % multiple
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, pad


def _tiles(N, C, block_rows):
    """(rows, cols) of one tile: whole rows where ``block_rows`` of them
    fit ``_TILE_BYTES`` as f32, else column tiles of a multiple of 128."""
    br = min(block_rows, N)
    if br * C * 4 <= _TILE_BYTES:
        return br, C
    return br, max(128, _TILE_BYTES // (4 * br) // 128 * 128)


def _amax_kernel(x_ref, a_ref):
    # the column axis is the inner grid axis: the (br, 1) output block
    # stays resident across it and accumulates the row maxima
    @pl.when(pl.program_id(1) == 0)
    def _():
        a_ref[...] = jnp.zeros_like(a_ref)

    a_ref[...] = jnp.maximum(a_ref[...], jnp.max(
        jnp.abs(x_ref[...].astype(jnp.float32)), axis=-1, keepdims=True))


def _quant_kernel(x_ref, s_ref, q_ref):
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / s_ref[...]),
                 -127, 127)
    q_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(
        x_ref.dtype)


def quantize_int8(x, *, block_rows=256, interpret=None):
    """x: (N, C) -> (q int8 (N, C), scale fp32 (N, 1)).

    Two passes over (rows, cols) tiles: the row maxima, then the scaled
    rounding.  Zero padding (rows to the tile, columns to the tile) leaves
    every real row's maximum unchanged and is sliced off."""
    N, C = x.shape
    interpret = resolve_interpret(interpret)
    br, bc = _tiles(N, C, block_rows)
    x, _ = _pad_axis(x, 0, br)
    x, _ = _pad_axis(x, 1, bc)
    grid = (x.shape[0] // br, x.shape[1] // bc)
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    row = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    amax = pl.pallas_call(
        _amax_kernel, grid=grid, in_specs=[tile], out_specs=row,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32),
        interpret=interpret,
    )(x)
    s = jnp.maximum(amax, 1e-12) / 127.0
    q = pl.pallas_call(
        _quant_kernel, grid=grid, in_specs=[tile, row], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int8),
        interpret=interpret,
    )(x, s)
    return q[:N, :C], s[:N]


def dequantize_int8(q, scale, dtype=jnp.float32, *, block_rows=256,
                    interpret=None):
    """q: (N, C) int8, scale: (N, 1) -> (N, C) dtype."""
    N, C = q.shape
    interpret = resolve_interpret(interpret)
    br, bc = _tiles(N, C, block_rows)
    q, _ = _pad_axis(q, 0, br)
    q, _ = _pad_axis(q, 1, bc)
    scale, _ = _pad_axis(scale, 0, br)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(q.shape[0] // br, q.shape[1] // bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((br, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(q.shape, dtype),
        interpret=interpret,
    )(q, scale)
    return x[:N, :C]
