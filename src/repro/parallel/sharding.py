"""Logical-axis sharding: rules, pruning, activation constraints, param specs.

Models are written against *logical* axes ("batch", "embed", "heads", "mlp",
"expert", "vocab", "kv_seq", ...).  A ``ShardingCtx`` maps logical axes to mesh
axes for the current (mesh x shape-kind) and is installed by the step
factories; when no ctx is installed (unit tests, single-device smoke runs) all
helpers are no-ops.

Divisibility: jit rejects shardings whose dimension is not divisible by the
mesh-axis product, so ``safe_spec`` prunes per-dimension any mesh axes that do
not divide the (global) dim.  ``best_spec`` picks the first fully-divisible
candidate from a priority list (used e.g. for KV caches: shard kv-heads on
'model' when divisible, else split the cache sequence flash-decode style).
"""
from __future__ import annotations

import math
import re
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel import compat


# mesh axes that a logical axis maps to (a tuple means "shard over both")
LogicalRules = dict[str, tuple[str, ...]]


def train_rules(multi_pod: bool, sequence_parallel: bool = False) -> LogicalRules:
    batch = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": batch,
        "seq": (),             # sequence replicated during training
        # Megatron-SP: the residual stream is sequence-sharded over 'model'
        # between TP regions, turning per-layer activation all-reduces into
        # all-gather + reduce-scatter pairs (half the wire bytes).
        "seq_sp": ("model",) if sequence_parallel else (),
        "kv_seq": (),
        "embed": ("data",),    # FSDP/ZeRO param dim
        "heads": ("model",),
        "kv": ("model",),
        "mlp": ("model",),
        "expert": ("model",),
        "vocab": ("model",),
        "cache_seq": ("model",),   # flash-decode style cache split
        "stage": (),
    }


def decode_rules(multi_pod: bool, long_context: bool) -> LogicalRules:
    r = train_rules(multi_pod)
    if long_context:
        # batch=1: every mesh axis shards the KV-cache / state sequence
        r["batch"] = ()
        r["cache_seq"] = (("pod", "data", "model") if multi_pod
                          else ("data", "model"))
    return r


@dataclass
class ShardingCtx:
    mesh: Mesh
    rules: LogicalRules
    enabled: bool = True

    def mesh_axes(self, logical: Optional[str]) -> tuple[str, ...]:
        if logical is None:
            return ()
        axes = self.rules.get(logical, ())
        return tuple(a for a in axes if a in self.mesh.axis_names)

    def axis_size(self, logical: str) -> int:
        return math.prod(self.mesh.shape[a] for a in self.mesh_axes(logical))


_local = threading.local()


def set_ctx(ctx: Optional[ShardingCtx]) -> None:
    _local.ctx = ctx


def get_ctx() -> Optional[ShardingCtx]:
    return getattr(_local, "ctx", None)


class use_ctx:
    """Context manager installing a ShardingCtx."""

    def __init__(self, ctx: Optional[ShardingCtx]):
        self.ctx = ctx

    def __enter__(self):
        self.prev = get_ctx()
        set_ctx(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        set_ctx(self.prev)


def safe_spec(shape: Sequence[int], logical: Sequence[Optional[str]],
              ctx: Optional[ShardingCtx] = None) -> P:
    """PartitionSpec for ``shape`` given logical axes, pruning non-divisible axes."""
    ctx = ctx or get_ctx()
    assert ctx is not None
    assert len(shape) == len(logical), (shape, logical)
    out = []
    for dim, name in zip(shape, logical):
        axes = ctx.mesh_axes(name)
        # prune greedily: keep the longest prefix of mesh axes that divides dim
        kept: list[str] = []
        prod = 1
        for a in axes:
            n = ctx.mesh.shape[a]
            if dim % (prod * n) == 0:
                kept.append(a)
                prod *= n
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def best_spec(shape: Sequence[int], candidates: Sequence[Sequence[Optional[str]]],
              ctx: Optional[ShardingCtx] = None) -> P:
    """First candidate whose every named logical axis fully divides its dim."""
    ctx = ctx or get_ctx()
    assert ctx is not None
    for logical in candidates:
        ok = True
        for dim, name in zip(shape, logical):
            size = math.prod(ctx.mesh.shape[a] for a in ctx.mesh_axes(name))
            if size > 1 and dim % size != 0:
                ok = False
                break
        if ok:
            return safe_spec(shape, logical, ctx)
    return safe_spec(shape, candidates[-1], ctx)


def _current_mesh(ctx: ShardingCtx):
    """Inside shard_map the ambient abstract mesh (with Manual axes) must be
    used for constraints; otherwise the ctx's concrete mesh."""
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and set(am.axis_names) == set(ctx.mesh.axis_names):
        return am
    return ctx.mesh


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint against the current ctx (no-op when unset)."""
    ctx = get_ctx()
    if ctx is None or not ctx.enabled:
        return x
    spec = safe_spec(x.shape, logical, ctx)
    return jax.lax.with_sharding_constraint(
        x, compat.named_sharding(_current_mesh(ctx), spec))


def constrain_best(x: jax.Array, candidates: Sequence[Sequence[Optional[str]]]) -> jax.Array:
    ctx = get_ctx()
    if ctx is None or not ctx.enabled:
        return x
    spec = best_spec(x.shape, candidates, ctx)
    return jax.lax.with_sharding_constraint(
        x, compat.named_sharding(_current_mesh(ctx), spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules: path-regex -> logical axes per dimension.
# Kernels are flattened 2D (in, out); stacked layer params get a leading group
# dim which is handled by the "layers/" prefix (prepends None).
# ---------------------------------------------------------------------------

PARAM_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    # Megatron-style vocab-parallel embedding: feature dim replicated —
    # 2D-sharded tables trip XLA's gather partitioner (full remat warning +
    # CPU-backend crash) and the logits matmul wants vocab x replicated-D.
    (r"embed/embedding$",        ("vocab", None)),
    (r"pos_embed/embedding$",    (None, "embed")),
    (r"lm_head/kernel$",         ("embed", "vocab")),
    (r"attn/(q|k|v)/kernel$",    ("embed", "heads")),
    (r"attn/o/kernel$",          ("heads", "embed")),
    (r"attn/(q|k|v|o)/bias$",    (None,)),
    (r"(mlp|shared_mlp)/w(i|g)/kernel$", ("embed", "mlp")),
    (r"(mlp|shared_mlp)/wo/kernel$",     ("mlp", "embed")),
    (r"(mlp|shared_mlp)/w./bias$",       (None,)),
    (r"moe/router/kernel$",      ("embed", None)),
    (r"moe/w(i|g)/kernel$",      ("expert", "embed", None)),
    (r"moe/wo/kernel$",          ("expert", None, "embed")),
    (r"mamba/in_proj/kernel$",   ("embed", "mlp")),
    (r"mamba/conv/kernel$",      (None, "mlp")),
    (r"mamba/x_proj/kernel$",    ("mlp", None)),
    (r"mamba/dt_proj/kernel$",   (None, "mlp")),
    (r"mamba/dt_proj/bias$",     ("mlp",)),
    (r"mamba/(A_log|D)$",        ("mlp", None)),
    (r"mamba/out_proj/kernel$",  ("mlp", "embed")),
    (r"rwkv/(r|k|v|g)/kernel$",  ("embed", "heads")),
    (r"rwkv/o/kernel$",          ("heads", "embed")),
    # LoRA factors are tiny (<3MB): sharding their output dim on 'model'
    # would turn every ddlerp/decay LoRA into a (B,T,5,D) partial-sum
    # all-reduce (measured 5x1.1GB/layer on rwkv6-7b) — replicate instead.
    (r"rwkv/(w_lora_a|mix_lora_a)/kernel$", ("embed", None)),
    (r"rwkv/w_lora_b/kernel$",   (None, None)),
    (r"rwkv/mix_lora_b/kernel$", (None, None, None)),
    (r"rwkv/(time_decay|time_first|bonus)$", ("heads",)),
    (r"rwkv/(mix_.*|ln_x/.*)$",  (None,)),
    (r"cmlp/wk/kernel$",         ("embed", "mlp")),
    (r"cmlp/wv/kernel$",         ("mlp", "embed")),
    (r"cmlp/wr/kernel$",         ("embed", "heads")),
    (r"(vit_proj|frame_proj)/kernel$", (None, "embed")),
    # norms / small vectors: replicated
    (r".*(scale|bias|mix|gamma|beta)$", None),
    (r".*$",                     None),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_param(path: str, shape: Sequence[int], ctx: ShardingCtx) -> P:
    ndim = len(shape)
    stacked = path.startswith("layers/") or "/layers/" in path
    for pat, logical in PARAM_RULES:
        if re.search(pat, path):
            if logical is None:
                return P()
            logical = tuple(logical)
            if stacked and len(logical) == ndim - 1:
                logical = (None,) + logical
            if len(logical) != ndim:
                # rank mismatch (e.g. scalars): replicate
                return P()
            return safe_spec(shape, logical, ctx)
    return P()


def param_specs(params_shape_tree, ctx: ShardingCtx):
    """Tree of PartitionSpec mirroring a (Shape/Array) param tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for_param(_path_str(path), leaf.shape, ctx),
        params_shape_tree)


def param_shardings(params_shape_tree, ctx: ShardingCtx):
    return jax.tree_util.tree_map(
        lambda spec: compat.named_sharding(ctx.mesh, spec),
        param_specs(params_shape_tree, ctx),
        is_leaf=lambda x: isinstance(x, P))
