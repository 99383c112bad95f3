"""Hand-scheduled collectives with in-path transforms.

This is the paper's "embedded function mode" mapped to TPU: instead of
offloading packet transforms to a SmartNIC in the network path, we fuse
transforms (int8 quantization with error feedback) into the gradient
all-reduce that crosses the slow ('pod' / DCN-like) axis.

Two implementations are provided, mirroring the paper's kernel-stack vs
user-space-stack (DPDK) comparison:

  * ``compressed_psum``  — all_to_all + local reduce + all_gather, int8 wire
    format in both phases (~4x less DCN traffic than fp32).
  * ``ring_allreduce``   — explicit ppermute ring reduce-scatter/all-gather
    with an optional per-hop wire dtype; with ``wire_int8`` *both* phases
    (per-hop requantize and the final all-gather) ship int8 + fp32 scales,
    ~2/8 of the stock fp32 wire at large n.

The quantize/dequantize hot spots route through ``kernels/ops.py`` — the
single policy-dispatch door — which picks the Pallas kernels for payloads
above ``PALLAS_QUANT_MIN_SIZE`` (``quant_impl="auto"``, the default) and
resolves compiled vs interpreted per backend.  ``reduce_gradients`` fuses the
gradient tree into a few bucket buffers (``parallel/buckets.py``) so a
multi-leaf tree costs one collective chain per *bucket* plus one grouped
``pmean`` for the small passthrough leaves, instead of one chain per leaf;
chain issue order is a *schedule* (``parallel/overlap.py``): strictly
serial, or software-pipelined so bucket ``i``'s exchange is in flight
while bucket ``i+1`` packs.

All functions run inside ``shard_map`` with the target axis manual.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.quant import PALLAS_QUANT_MIN_SIZE  # noqa: F401 — the
#   auto-dispatch threshold, re-exported for callers/tests of this module
from repro.parallel import buckets as B
from repro.parallel import overlap as O

DEFAULT_BUCKET_BYTES = B.DEFAULT_BUCKET_BYTES
MIN_COMPRESS_SIZE = B.MIN_COMPRESS_SIZE


# ---------------------------------------------------------------------------
# collective-chain accounting (trace-time)
# ---------------------------------------------------------------------------

# Number of collective chains (quantize->exchange->dequantize sequences, or
# grouped pmean calls) issued while tracing.  Incremented at Python trace
# time, so counting a jitted function means tracing it fresh (e.g.
# ``jax.jit(f).lower(...)``) after ``reset_chain_count()``.
_CHAIN_COUNT = 0


def _count_chain() -> None:
    global _CHAIN_COUNT
    _CHAIN_COUNT += 1


def reset_chain_count() -> None:
    global _CHAIN_COUNT
    _CHAIN_COUNT = 0


def chain_count() -> int:
    return _CHAIN_COUNT


# ---------------------------------------------------------------------------
# int8 (de)quantization — the in-path transform
# ---------------------------------------------------------------------------

def _quantize_int8_jnp(x: jax.Array, axis: int = -1):
    """Shape-preserving plain-jnp quantization — no reshape, no custom
    call, so GSPMD can partition it across auto-sharded dims (the
    ``pairwise_int8_allreduce`` requirement)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize_int8_jnp(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def quantize_int8(x: jax.Array, axis: int = -1):
    """Symmetric per-slice int8 quantization.  Returns (q, scale).

    Last-axis payloads route through ``kernels.ops`` — the one policy
    dispatch door, which picks the Pallas kernel or the jnp reference per
    ``runtime.policy()`` and payload size; other axes quantize in plain
    jnp (the kernels are rowwise-only).  Only the *chunked* collectives
    (whose payloads are manual over the target axis by construction) call
    this; shape-preserving ``pairwise_int8_allreduce`` keeps the jnp
    transform so auto-sharded payloads stay partitionable."""
    if x.ndim >= 1 and axis in (-1, x.ndim - 1):
        from repro.kernels import ops
        C = x.shape[-1]
        q, s = ops.quantize_int8(x.reshape(-1, C))
        return q.reshape(x.shape), s.reshape(x.shape[:-1] + (1,))
    return _quantize_int8_jnp(x, axis)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    if (q.ndim >= 1 and scale.ndim == q.ndim
            and scale.shape[:-1] == q.shape[:-1] and scale.shape[-1] == 1):
        from repro.kernels import ops
        C = q.shape[-1]
        out = ops.dequantize_int8(q.reshape(-1, C), scale.reshape(-1, 1))
        return out.reshape(q.shape)
    return _dequantize_int8_jnp(q, scale)


# ---------------------------------------------------------------------------
# compressed all-reduce (all_to_all formulation)
# ---------------------------------------------------------------------------

def _to_chunks(x: jax.Array, n: int):
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n, -1), pad


def compressed_psum(x: jax.Array, axis_name: str, mean: bool = True):
    """int8-wire all-reduce over ``axis_name``.

    Both exchange phases are compressed: the all_to_all ships int8 chunk
    rows + fp32 scales, and the second phase all_gathers the requantized
    partial sums the same way.  Returns (reduced, residual) where
    ``residual = x - dequant(quant(x))`` is this device's local
    quantization error for error feedback.
    """
    _count_chain()
    n = jax.lax.axis_size(axis_name)
    chunks, pad = _to_chunks(x, n)                       # (n, c)
    q, s = quantize_int8(chunks)                         # int8 (n,c), (n,1)
    residual = (chunks - dequantize_int8(q, s)).reshape(-1)
    residual = residual[:residual.size - pad] if pad else residual
    residual = residual.reshape(x.shape).astype(x.dtype)

    # exchange: device i receives chunk i from every pod
    q = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)                   # (n, c)
    s = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)                   # (n, 1)
    partial = jnp.sum(dequantize_int8(q, s), axis=0)     # (c,)
    if mean:
        partial = partial / n
    q2, s2 = quantize_int8(partial[None])                # (1,c)
    q2 = jax.lax.all_gather(q2[0], axis_name)            # (n, c)
    s2 = jax.lax.all_gather(s2[0], axis_name)            # (n, 1)
    out = dequantize_int8(q2, s2).reshape(-1)
    if pad:
        out = out[:out.size - pad]
    return out.reshape(x.shape).astype(x.dtype), residual


# ---------------------------------------------------------------------------
# shape-preserving pairwise int8 exchange (small pod counts)
# ---------------------------------------------------------------------------

def pairwise_int8_allreduce(x: jax.Array, axis_name: str, mean: bool = True):
    """int8 ring broadcast-accumulate WITHOUT reshaping the payload.

    The a2a/ring formulations flatten to (n, c) chunks — inside a shard_map
    that is manual only over 'pod', that reshape crosses the auto-sharded
    dims and GSPMD must all-gather the whole gradient first (measured 6x
    regression on jamba-398B).  Here the tensor keeps its (sharded) shape:
    each pod ppermutes its int8 copy around the ring and accumulates.

    Wire: (n-1) x 1 B/elem vs stock bf16 all-reduce 2(n-1)/n x 2 B/elem —
    a 2x DCN saving at n=2 pods (the production mesh); prefer the chunked
    forms only when n is large AND the payload is pod-manual."""
    _count_chain()
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    xf = x.astype(jnp.float32)
    # plain-jnp transform on purpose: the payload may be auto-sharded over
    # model dims, and the Pallas path's reshape + opaque custom call would
    # force GSPMD to all-gather it — the regression this function avoids
    q, s = _quantize_int8_jnp(xf)                 # rowwise scales, same shape
    residual = (xf - _dequantize_int8_jnp(q, s)).astype(x.dtype)
    acc = _dequantize_int8_jnp(q, s)
    for _ in range(n - 1):
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        acc = acc + _dequantize_int8_jnp(q, s)
    if mean:
        acc = acc / n
    return acc.astype(x.dtype), residual


# ---------------------------------------------------------------------------
# explicit ring all-reduce (ppermute formulation)
# ---------------------------------------------------------------------------

def _take(chunks: jax.Array, idx: jax.Array) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(chunks, idx, 1, axis=0)[0]


def ring_allreduce(x: jax.Array, axis_name: str, mean: bool = True,
                   wire_int8: bool = False):
    """Ring reduce-scatter + all-gather via collective_permute.

    With ``wire_int8`` every hop carries int8 payloads (per-hop requantize)
    AND the final all-gather ships the requantized owned chunk — the wire
    is fully compressed, ~2/8 of the stock fp32 bytes at large n.  Returns
    (reduced, residual).
    """
    _count_chain()
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunks, pad = _to_chunks(x, n)                       # (n, c)

    residual = jnp.zeros_like(x, dtype=x.dtype)
    if wire_int8:
        q, s = quantize_int8(chunks)
        res = (chunks - dequantize_int8(q, s)).reshape(-1)
        res = res[:res.size - pad] if pad else res
        residual = res.reshape(x.shape).astype(x.dtype)
        chunks = dequantize_int8(q, s)

    def hop(z):
        if not wire_int8:
            return jax.lax.ppermute(z, axis_name, perm)
        qz, sz = quantize_int8(z[None])
        qz = jax.lax.ppermute(qz[0], axis_name, perm)
        # keep sz at (1, 1): a (1,)-shaped scale fails the rowwise-dispatch
        # guard and would silently drop the hot per-hop dequant to jnp
        sz = jax.lax.ppermute(sz, axis_name, perm)
        return dequantize_int8(qz[None], sz)[0]

    # reduce-scatter: after n-1 hops, device i owns chunk (i+1) % n
    acc = _take(chunks, me)
    for t in range(n - 1):
        acc = hop(acc)
        acc = acc + _take(chunks, (me - 1 - t) % n)
    if mean:
        acc = acc / n
    # all-gather of owned chunks, rotated back into order; with wire_int8
    # the gather phase is compressed too (quantize acc before all_gather)
    if wire_int8:
        qa, sa = quantize_int8(acc[None])                # (1,c), (1,1)
        qg = jax.lax.all_gather(qa[0], axis_name)        # (n, c) int8
        sg = jax.lax.all_gather(sa[0], axis_name)        # (n, 1) fp32
        ag = dequantize_int8(qg, sg)
    else:
        ag = jax.lax.all_gather(acc, axis_name)          # row j = chunk (j+1)%n
    out = jnp.roll(ag, 1, axis=0).reshape(-1)
    if pad:
        out = out[:out.size - pad]
    return out.reshape(x.shape).astype(x.dtype), residual


# ---------------------------------------------------------------------------
# gradient-tree reduction with error feedback
# ---------------------------------------------------------------------------

def _chain(x, axis_name: str, method: str):
    """One compressed (or explicit) all-reduce chain for one payload."""
    if method == "int8_a2a":
        return compressed_psum(x, axis_name)
    if method == "int8_pairwise":
        return pairwise_int8_allreduce(x, axis_name)
    if method == "int8_ring":
        return ring_allreduce(x, axis_name, wire_int8=True)
    if method == "ring":
        return ring_allreduce(x, axis_name)
    raise ValueError(method)


def _grouped_pmean(leaves, axis_name: str):
    """One pmean *call* for a whole list of leaves — XLA emits a single
    variadic all-reduce, so this counts as one collective chain."""
    _count_chain()
    return jax.lax.pmean(leaves, axis_name)


def reduce_gradients(grads, axis_name: str, method: str = "stock",
                     errors=None, *, bucketed: Optional[bool] = None,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     overlap: Optional[bool] = None,
                     fabric=None):
    """Cross-'pod' gradient reduction with error feedback.

    method: stock | int8_a2a | int8_ring | int8_pairwise | ring.
    ``errors`` is the error-feedback tree (or None); returns
    (grads, errors), both with the input tree structure.

    With ``bucketed`` the tree is fused into size-capped fp32 buckets
    (``bucket_bytes`` apiece): one collective chain per bucket, plus a
    single grouped ``pmean`` for the leaves below ``MIN_COMPRESS_SIZE``.
    ``bucketed=None`` (the default) resolves per method: True for the
    chunked forms (``int8_a2a``/``int8_ring``/``ring``), False for
    ``int8_pairwise``, whose whole point is *not* reshaping the payload
    (packing would reintroduce the cross-auto-axis gather it avoids).
    ``bucketed=False`` keeps the legacy leaf-wise chains — measured
    against the bucketed path by the ``inpath.bucketing`` experiment.

    ``overlap`` picks the bucket-chain schedule (``parallel/overlap.py``):
    False issues chains strictly one at a time (bucket ``i+1`` packs only
    after chain ``i`` has dequantized), True software-pipelines them
    (chain ``i`` in flight while bucket ``i+1`` packs), and None defers
    to ``runtime.policy()["overlap_schedule"]`` — whose ``auto`` default
    pipelines exactly when the plan yields more than one bucket.  Both
    schedules issue identical collectives (the HLO schedule test holds
    counts and wire bytes equal); only the dependency structure differs.
    Ignored on the leaf-wise path, whose chains are per-leaf and have no
    pack stage to hide.

    ``fabric`` (a ``repro.fabric.FabricCondition`` or None) injects a
    degraded-wire scenario into the chain issue: per-bucket common delays
    (latency, loss retries, jitter, bandwidth stretch) and a per-device
    straggler burn, spliced inside the schedule's dependency structure so
    serial and pipelined react differently (``fabric/inject.py``).  None
    or ``FabricCondition.clean()`` leave the traced program untouched —
    bit-identical outputs and identical collectives (guarded in tier-1).
    The legacy leaf-wise path (``bucketed=False``, incl. the
    ``int8_pairwise`` default) has no bucket schedule to perturb and
    ignores ``fabric``.
    """
    if bucketed is None:
        bucketed = method != "int8_pairwise"
    if fabric is not None and fabric.is_clean:
        fabric = None
    if method == "stock":
        if fabric is not None:
            # the unbucketed tree is one logical segment: gate every
            # leaf's pmean on one shared burn
            from repro.fabric.inject import ChainInjector  # fabric sits
            #   above parallel/ in the layering; import only when used
            nbytes = sum(g.size * g.dtype.itemsize
                         for g in jax.tree_util.tree_leaves(grads))
            inj = ChainInjector(fabric, axis_name, [nbytes])
            grads = inj.perturb_tree(grads)
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, axis_name), grads), errors

    if errors is None:
        errors = jax.tree_util.tree_map(jnp.zeros_like, grads)
    flat, treedef = jax.tree_util.tree_flatten(grads)
    eflat = jax.tree_util.tree_leaves(errors)

    if bucketed:
        outs, ress = _reduce_bucketed(flat, eflat, axis_name, method,
                                      bucket_bytes, overlap, fabric)
    else:
        outs, ress = _reduce_leafwise(flat, eflat, axis_name, method)
    return (jax.tree_util.tree_unflatten(treedef, outs),
            jax.tree_util.tree_unflatten(treedef, ress))


def _reduce_leafwise(flat, eflat, axis_name: str, method: str):
    """One collective chain per compressible leaf (the pre-bucketing path)."""
    outs, ress = [], []
    for g, e in zip(flat, eflat):
        if g.size < MIN_COMPRESS_SIZE:
            _count_chain()
            outs.append(jax.lax.pmean(g, axis_name))
            ress.append(jnp.zeros_like(e))
            continue
        out, res = _chain(g + e.astype(g.dtype), axis_name, method)
        outs.append(out)
        ress.append(res.astype(e.dtype))
    return outs, ress


def _reduce_bucketed(flat, eflat, axis_name: str, method: str,
                     bucket_bytes: int, overlap: Optional[bool] = None,
                     fabric=None):
    """One collective chain per fusion bucket; error feedback is packed
    into the buckets and the residual scattered back per leaf.  Chain
    issue order is a schedule (``parallel/overlap.py``): serial gates
    bucket ``i+1``'s pack on chain ``i``'s output, pipelined co-stages
    them dependency-free so the exchange can be in flight while the next
    bucket packs.  A non-clean ``fabric`` becomes the schedule's
    ``perturb``: each bucket's packed buffer is gated on that segment's
    sampled degradation before its chain issues (the grouped pmean of
    passthrough leaves rides clean — degradation applies to the wire's
    bulk payload, not the tail of tiny leaves)."""
    plan = B.plan_buckets(flat, bucket_bytes=bucket_bytes,
                          min_compress_size=MIN_COMPRESS_SIZE)
    overlap = O.resolve_overlap(overlap, plan.n_buckets)

    def pack_one(i):
        # gradient bucket + its error-feedback bucket, fused at pack time
        # so the schedule sees one buffer per stage
        return B.pack_bucket(plan, i, flat) + B.pack_bucket(plan, i, eflat)

    perturb = None
    if fabric is not None and not fabric.is_clean:
        from repro.fabric.inject import ChainInjector  # layered above us
        inj = ChainInjector(fabric, axis_name,
                            [4 * s for s in plan.bucket_sizes()])
        perturb = inj.perturb

    chains = O.run_schedule(
        plan.n_buckets, pack_one,
        lambda buf: _chain(buf, axis_name, method), overlap,
        perturb=perturb)
    red = [o for o, _ in chains]
    res = [r for _, r in chains]
    outs = B.unpack(plan, red, like=flat)
    ress = B.unpack(plan, res, like=eflat)
    if plan.passthrough:
        small = _grouped_pmean([flat[i] for i in plan.passthrough],
                               axis_name)
        for j, i in enumerate(plan.passthrough):
            outs[i] = small[j]
            ress[i] = jnp.zeros_like(eflat[i])
    return outs, ress
