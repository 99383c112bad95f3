"""Public jit'd wrappers for the Pallas kernels, with policy dispatch.

The ONE place ``runtime.policy()`` decides which implementation backs each
hot-spot op: callers (models/attention.py, models/rwkv6.py,
parallel/collectives.py) go through these wrappers rather than re-reading
the policy.  ``pallas_interpret=None`` (the default) resolves per backend
via ``kernels.quant.resolve_interpret`` — compiled Mosaic on TPU/GPU,
interpreter on this CPU container (where the kernels are validated against
kernels/ref.py in tests).  ``quant_impl="auto"`` routes payloads above
``quant.PALLAS_QUANT_MIN_SIZE`` through the Pallas quant kernels and the
rest through the jnp reference (the launch-overhead profitability rule).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import runtime
from repro.kernels import expert_gmm as _eg
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import paged_mla_attention as _pm
from repro.kernels import quant as _q
from repro.kernels import ref as _ref
from repro.kernels import rwkv6_scan as _rs


def _interp() -> bool:
    return _q.resolve_interpret(runtime.policy()["pallas_interpret"])


def use_pallas_quant(size: int) -> bool:
    """Whether a quant payload of ``size`` elements takes the Pallas path
    under the current policy (``pallas`` forces, ``xla`` forbids, ``auto``
    keys on ``quant.PALLAS_QUANT_MIN_SIZE``)."""
    impl = runtime.policy()["quant_impl"]
    return impl == "pallas" or (impl == "auto"
                                and size >= _q.PALLAS_QUANT_MIN_SIZE)


# The runtime policy is resolved OUTSIDE the jitted inner functions and
# threaded through as a static argument: a jit cache keys on avals and
# statics only, so a policy read *inside* the traced body (the previous
# shape of these wrappers) is frozen into the first trace — flipping
# ``runtime.policy()`` with an already-seen shape silently reused the
# stale dispatch.  With ``interpret`` static, a flip is a new cache entry
# and retraces (regression-tested in test_kernels.py).

@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def _flash_attention(q, k, v, *, causal, window, block_q, block_k,
                     interpret):
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q=128, block_k=128):
    return _flash_attention(q, k, v, causal=causal, window=window,
                            block_q=block_q, block_k=block_k,
                            interpret=_interp())


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _rwkv6_scan(r, k, v, w, u, s0, *, chunk, interpret):
    return _rs.rwkv6_scan_fwd(r, k, v, w, u, s0, chunk=chunk,
                              interpret=interpret)


def rwkv6_scan(r, k, v, w, u, s0=None, *, chunk=64):
    return _rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=_interp())


@partial(jax.jit, static_argnames=("buffer_depth", "use_kernel",
                                   "interpret"))
def _paged_attention(q, pool, tables, lengths, layer, *, buffer_depth,
                     use_kernel, interpret):
    if use_kernel:
        return _pa.paged_attention_fwd(q, pool, tables, lengths, layer,
                                       buffer_depth=buffer_depth,
                                       interpret=interpret).astype(q.dtype)
    return _pa.paged_attention_xla(q, pool, tables, lengths, layer,
                                   buffer_depth=buffer_depth)


def use_paged_kernel() -> bool:
    """Whether paged attention takes the Pallas kernel under the current
    policy: ``pallas`` forces it, ``xla`` forbids it, ``auto`` keys on the
    backend the way ``quant.resolve_interpret`` does — the kernel's manual
    DMA pipeline only pays where Mosaic compiles it, so backends that
    would run the interpreter route through the XLA twin instead (same
    math and page walk; ``kernels/paged_attention.py``)."""
    impl = runtime.policy()["paged_attention_impl"]
    if impl == "auto":
        return not _q.resolve_interpret(None)
    return impl == "pallas"


def paged_attention(q, pool, tables, lengths, layer=0, *,
                    buffer_depth=2):
    """Policy-dispatched ragged paged-attention decode over ``pool``'s
    layer ``layer`` (see ``kernels/paged_attention.py`` for shapes: a
    stacked 5-D pool or one 4-D layer), ``buffer_depth`` blocks of page
    loads in flight."""
    return _paged_attention(q, pool, tables, lengths, layer,
                            buffer_depth=buffer_depth,
                            use_kernel=use_paged_kernel(),
                            interpret=_interp())


@partial(jax.jit, static_argnames=("latent", "sm_scale", "buffer_depth",
                                   "use_kernel", "interpret"))
def _paged_mla_attention(q, pool, tables, lengths, layer, *, latent,
                         sm_scale, buffer_depth, use_kernel, interpret):
    if use_kernel:
        return _pm.paged_mla_attention_fwd(
            q, pool, tables, lengths, layer, latent=latent,
            sm_scale=sm_scale, buffer_depth=buffer_depth,
            interpret=interpret).astype(q.dtype)
    return _pm.paged_mla_attention_xla(q, pool, tables, lengths, layer,
                                       latent=latent, sm_scale=sm_scale,
                                       buffer_depth=buffer_depth)


def paged_mla_attention(q, pool, tables, lengths, layer=0, *, latent,
                        sm_scale, buffer_depth=2):
    """Policy-dispatched paged latent attention over ``pool``'s layer
    ``layer`` (``kernels/paged_mla_attention.py``): the Pallas kernel or
    its XLA twin, picked as ``paged_attention`` picks."""
    return _paged_mla_attention(q, pool, tables, lengths, layer,
                                latent=latent, sm_scale=float(sm_scale),
                                buffer_depth=buffer_depth,
                                use_kernel=use_paged_kernel(),
                                interpret=_interp())


def expert_gmm(lhs, rhs, group_sizes, layer=None, *, out_dtype=jnp.float32):
    """Rows sorted by expert times their expert's weights
    (``kernels/expert_gmm.py``): the Pallas kernel where it compiles
    (the policy's ``pallas_interpret`` resolves to compiled), else its
    XLA twin. ``rhs`` is (E, k, n), or (L, E, k, n)
    read at ``layer``. Rows past ``sum(group_sizes)`` are unspecified."""
    if _interp():
        return _eg.expert_gmm_xla(lhs, rhs, group_sizes, layer,
                                  out_dtype=out_dtype)
    return _eg.expert_gmm_fwd(lhs, rhs, group_sizes, layer,
                              out_dtype=out_dtype, interpret=False)


# NOTE: unlike the attention/rwkv wrappers these are deliberately NOT
# jitted: a jit cache keys on avals only, so a runtime-policy flip with an
# already-seen shape would silently reuse the stale dispatch.  Callers are
# inside jit/shard_map traces anyway (collectives, stressors time a jitted
# lambda), so nothing is lost.

def quantize_int8(x):
    if use_pallas_quant(x.size):
        return _q.quantize_int8(x, interpret=_interp())
    return _ref.quantize_int8_ref(x)


def dequantize_int8(q, scale, dtype=jnp.float32):
    if use_pallas_quant(q.size):
        return _q.dequantize_int8(q, scale, dtype=dtype, interpret=_interp())
    return _ref.dequantize_int8_ref(q, scale).astype(dtype)
