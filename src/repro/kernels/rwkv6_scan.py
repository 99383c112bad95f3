"""RWKV-6 chunked WKV Pallas TPU kernel.

Grid (B*H, T/chunk): TPU grids iterate sequentially, so the cross-chunk
state S (dh x dh, fp32) lives in VMEM scratch and carries between chunk
steps — the same trick flash attention uses for its online-softmax carry.
Within a chunk the strictly-causal contribution is a (chunk x chunk)
masked matmul on decay-rescaled r/k (flash-linear-attention formulation).

dh = 64 for every RWKV arch — one chunk of work is (64x64) matmuls against
(chunk=64) tiles, sized for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant import resolve_interpret

CLIP = 30.0


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sT_ref, s_scratch, *, chunk, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scratch[...] = s0_ref[...]

    r = r_ref[...].astype(jnp.float32)            # (L, dh)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)            # (1, dh)
    S = s_scratch[...]                            # (dh, dh)

    lw = jnp.log(jnp.maximum(w, 1e-12))
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    mi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular ones
    # matmul: Mosaic has no cumsum lowering, and HIGHEST keeps the f32
    # log-decays exact on the MXU
    tril = jnp.where(li >= mi, 1.0, 0.0).astype(jnp.float32)
    cl = jax.lax.dot(tril, lw, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    cl_ex = cl - lw
    r_d = r * jnp.exp(cl_ex)
    k_d = k * jnp.exp(jnp.clip(-cl, max=CLIP))
    scores = jax.lax.dot_general(r_d, k_d, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(li > mi, scores, 0.0)      # strictly causal
    y = jax.lax.dot(scores, v, preferred_element_type=jnp.float32)
    y += jax.lax.dot(r_d, S, preferred_element_type=jnp.float32)
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)
    y += bonus * v
    y_ref[...] = y.astype(y_ref.dtype)

    dl = cl[-1:, :]                               # (1, dh) total chunk decay
    k_end = k * jnp.exp(jnp.clip(dl - cl, max=CLIP))
    S = jnp.exp(dl).T * S + jax.lax.dot_general(
        k_end, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_scratch[...] = S

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sT_ref[...] = S


def rwkv6_scan_fwd(r, k, v, w, u, s0=None, *, chunk=64, interpret=None):
    """r,k,v,w: (B, T, H, dh) fp32; u: (H, dh); s0: (B, H, dh, dh) or None.

    Returns (y (B,T,H,dh) fp32, S_T (B,H,dh,dh) fp32).

    ``interpret=None`` resolves per backend (``resolve_interpret``):
    compiled where Pallas has a real lowering, interpreter on CPU — the
    seed hardcoded ``True`` and interpreted everywhere."""
    interpret = resolve_interpret(interpret)
    B, T, H, dh = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    if s0 is None:
        s0 = jnp.zeros((B, H, dh, dh), jnp.float32)

    def flat(z):
        return z.transpose(0, 2, 1, 3).reshape(B * H, T, dh)

    rs, ks, vs, ws = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, 1, dh)
    s0f = s0.reshape(B * H, dh, dh)

    grid = (B * H, n_chunks)
    kern = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    y, sT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, 1, dh), lambda bh, ci: (bh, 0, 0)),
            pl.BlockSpec((None, dh, dh), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, dh), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, dh, dh), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, dh), jnp.float32),
            jax.ShapeDtypeStruct((B * H, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
    )(rs, ks, vs, ws, uf, s0f)
    return (y.reshape(B, H, T, dh).transpose(0, 2, 1, 3),
            sT.reshape(B, H, dh, dh))
