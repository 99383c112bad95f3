"""Serving steps: prefill and single-token decode, fully sharded.

decode cells: the KV cache is sequence-split over 'model' (flash-decode
style) for normal batched decode, and over every mesh axis for the
batch=1 long_500k cell (see parallel/sharding.decode_rules).

``make_continuous_cells`` packages the three cells the continuous-
batching engine drives (batch-1 prefill, vmapped slot decode, slot
insertion) as one :class:`ServeCells`, either single-device (the
engine's original plain-jit cells) or tensor-parallel over a
``("data", "model")`` mesh with explicit in/out shardings, so the
compiled steps are reshard-free at the call boundary and a silent
resharding shows up as a collective-count mismatch (guarded in
``tests/test_serve_sharded.py``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import registry
from repro.parallel import compat, sharding


def _ctx_for(mesh, shape: ShapeConfig):
    multi_pod = "pod" in mesh.axis_names
    long_ctx = shape.kind == "decode" and shape.global_batch == 1
    if shape.kind == "decode":
        rules = sharding.decode_rules(multi_pod, long_ctx)
    else:
        rules = sharding.train_rules(multi_pod)
    return sharding.ShardingCtx(mesh, rules)


def make_prefill_step(cfg: ArchConfig, shape: ShapeConfig, mesh,
                      cache_len=None):
    ctx = _ctx_for(mesh, shape)

    def step(params, batch):
        with sharding.use_ctx(ctx):
            return registry.prefill(cfg, params, batch, cache_len=cache_len)
    return step, ctx


def make_decode_step(cfg: ArchConfig, shape: ShapeConfig, mesh):
    ctx = _ctx_for(mesh, shape)

    def step(params, caches, batch):
        with sharding.use_ctx(ctx):
            logits, caches = registry.decode_step(cfg, params, batch, caches)
            return logits, caches
    return step, ctx


_CACHE_RULES = [
    # (key suffix, logical axes per dim, after the leading group dim)
    (("k", "v", "xk", "xv"), ("batch", "cache_seq", None, None)),
    (("conv",),              ("batch", None, "mlp")),
    (("ssm",),               ("batch", "mlp", None)),
    (("wkv",),               ("batch", "heads", None, None)),
    (("shift", "cm"),        ("batch", None, None)),
]


def cache_shardings(cache_shape, ctx: sharding.ShardingCtx):
    """Shardings for a decode-cache pytree (kv caches, ssm/rwkv states)."""
    def spec(path, leaf):
        key = str(path[-1].key) if hasattr(path[-1], "key") else ""
        for keys, logical in _CACHE_RULES:
            if key in keys and len(leaf.shape) == len(logical) + 1:
                return sharding.safe_spec(leaf.shape, (None,) + logical, ctx)
        return P()
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: compat.named_sharding(ctx.mesh, spec(path, leaf)),
        cache_shape)


def jit_decode_step(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """jit with explicit shardings for dry-run lowering."""
    step, ctx = make_decode_step(cfg, shape, mesh)
    params_shape = registry.abstract_params(cfg)
    pspec = sharding.param_shardings(params_shape, ctx)
    cache_shape = registry.abstract_decode_caches(
        cfg, shape.global_batch, shape.seq_len)
    cspec = cache_shardings(cache_shape, ctx)
    bspec = {}
    for k, v in registry.input_specs(cfg, shape).items():
        logical = ("batch",) + (None,) * (len(v.shape) - 1)
        bspec[k] = compat.named_sharding(
            ctx.mesh, sharding.safe_spec(v.shape, logical, ctx) if v.shape
            else P())
    jitted = jax.jit(step, in_shardings=(pspec, cspec, bspec),
                     donate_argnums=1)
    return jitted, ctx, params_shape, cache_shape


def jit_prefill_step(cfg: ArchConfig, shape: ShapeConfig, mesh):
    step, ctx = make_prefill_step(cfg, shape, mesh)
    params_shape = registry.abstract_params(cfg)
    pspec = sharding.param_shardings(params_shape, ctx)
    bspec = {}
    for k, v in registry.input_specs(cfg, shape).items():
        logical = ("batch",) + (None,) * (len(v.shape) - 1)
        bspec[k] = compat.named_sharding(
            ctx.mesh, sharding.safe_spec(v.shape, logical, ctx))
    jitted = jax.jit(step, in_shardings=(pspec, bspec))
    return jitted, ctx, params_shape


# ---------------------------------------------------------------------------
# continuous-engine cells: slot-stacked decode over the mesh
# ---------------------------------------------------------------------------

def slot_cache_shardings(slot_cache_shape, ctx: sharding.ShardingCtx):
    """Shardings for the continuous engine's *slot-stacked* decode caches.

    The engine stacks batch-1 caches along a leading slot axis, so every
    leaf carries two extra leading dims over the per-kind logical rules
    (slot, then the model-family group dim) — both replicated; the cache
    sequence stays split over 'model' exactly as in ``cache_shardings``.
    """
    def spec(path, leaf):
        key = str(path[-1].key) if hasattr(path[-1], "key") else ""
        for keys, logical in _CACHE_RULES:
            if key in keys and len(leaf.shape) == len(logical) + 2:
                return sharding.safe_spec(leaf.shape, (None, None) + logical,
                                          ctx)
        return P()
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: compat.named_sharding(ctx.mesh, spec(path, leaf)),
        slot_cache_shape)


def _in_ctx(ctx: sharding.ShardingCtx, fn: Callable) -> Callable:
    """``fn`` traced under the sharding rules ``ctx``, keeping its name:
    the engine's cells are jitted as ``serve_prefill``, ``serve_decode``
    and ``serve_insert`` in every build, so their XLA modules are
    ``jit_serve_*`` whether or not they run over a mesh."""
    @functools.wraps(fn)
    def wrapped(*args):
        with sharding.use_ctx(ctx):
            return fn(*args)
    return wrapped


@dataclass
class ServeCells:
    """The continuous engine's three compiled cells + their placements.

    ``mesh=None`` is the single-device build: ``put_params`` /
    ``init_slot_caches`` are identity/host placements and the cells are
    the engine's original plain ``jax.jit`` closures.  With a mesh, the
    cells carry explicit in/out shardings (params by the decode rules,
    slot caches via ``slot_cache_shardings``, tokens/positions replicated
    scalars) and the placement helpers ``device_put`` accordingly.
    """
    cfg: ArchConfig
    n_slots: int
    cache_len: int
    prefill: Callable        # (params, tokens[1,S]) -> (logits, base caches)
    decode: Callable         # (params, tok[slot,1,1], idx[slot], slot caches)
    insert: Callable         # (slot caches, base caches, slot) -> slot caches
    mesh: Optional[object] = None
    ctx: Optional[sharding.ShardingCtx] = None
    param_sharding: Optional[object] = None     # pytree of NamedSharding
    slot_sharding: Optional[object] = None      # slot-stacked cache pytree
    _decode_text: Optional[str] = field(default=None, repr=False)

    @property
    def tp_size(self) -> int:
        return 1 if self.mesh is None else int(dict(self.mesh.shape)
                                               .get("model", 1))

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def put_params(self, params):
        if self.param_sharding is None:
            return params
        return jax.device_put(params, self.param_sharding)

    def init_slot_caches(self):
        base = registry.init_decode_caches(self.cfg, 1, self.cache_len)
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * self.n_slots), base)
        if self.slot_sharding is None:
            return stacked
        return jax.device_put(stacked, self.slot_sharding)

    # -- HLO inspection (tests + the sharded-sweep experiment) -------------

    def decode_hlo_text(self, params) -> str:
        """Compiled HLO of the slot-decode cell (cached; abstract args, so
        this never touches — or donates — live buffers)."""
        if self._decode_text is None:
            p = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            tok = jax.ShapeDtypeStruct((self.n_slots, 1, 1), jnp.int32)
            idx = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
            caches = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self.init_slot_caches())
            self._decode_text = self.decode.lower(
                p, tok, idx, caches).compile().as_text()
        return self._decode_text

    def decode_collective_counts(self, params) -> dict:
        """Trip-count-weighted per-kind collective counts of the compiled
        slot-decode step — the silent-resharding guard: an implicit
        resharding XLA inserts at the call boundary changes these."""
        from repro.analysis import hlo
        ops = hlo.parse_collectives(self.decode_hlo_text(params),
                                    self.n_devices)
        return dict(hlo.collective_counts(ops))


@dataclass
class PagedServeCells:
    """The paged engine's three compiled cells + their placements.

    The dense :class:`ServeCells` stack per-slot caches; here the KV state
    is ONE physical page pool per layer (``serve/paged.py``) and the slot
    dimension lives in the block *tables* — decode takes every slot's
    token/position plus the (n_slots, max_pages) table array and returns
    updated pool state.  Sharded builds split the pool over 'model' on the
    fused head axis and replicate tables/tokens, mirroring the dense
    cells' reshard-free call boundary.
    """
    cfg: ArchConfig
    n_slots: int
    cache_len: int
    block_size: int
    n_pages: int
    buffer_depth: int
    prefill: Callable        # (params, tokens[1,S]) -> (logits, base caches)
    decode: Callable         # (params, tok[S,1], idx[S], pool, tables[S,mp])
    #                          -> (logits, pool[, expert load])
    insert: Callable         # (pool, base caches, table_row[mp]) -> pool
    mesh: Optional[object] = None
    ctx: Optional[sharding.ShardingCtx] = None
    param_sharding: Optional[object] = None     # pytree of NamedSharding
    pool_sharding: Optional[object] = None      # pool pytree of NamedSharding
    _decode_text: Optional[str] = field(default=None, repr=False)

    @property
    def max_pages(self) -> int:
        return self.cache_len // self.block_size

    @property
    def tp_size(self) -> int:
        return 1 if self.mesh is None else int(dict(self.mesh.shape)
                                               .get("model", 1))

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def put_params(self, params):
        if self.param_sharding is None:
            return params
        return jax.device_put(params, self.param_sharding)

    def init_pool(self):
        from repro.serve import paged
        pool = paged.init_kv_pool(self.cfg, self.n_pages, self.block_size)
        if self.pool_sharding is None:
            return pool
        return jax.device_put(pool, self.pool_sharding)

    def decode_hlo_text(self, params) -> str:
        """Compiled HLO of the paged-decode cell (abstract args; cached)."""
        if self._decode_text is None:
            p = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            tok = jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32)
            idx = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
            pool = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                jax.eval_shape(self.init_pool))
            tbl = jax.ShapeDtypeStruct((self.n_slots, self.max_pages),
                                       jnp.int32)
            self._decode_text = self.decode.lower(
                p, tok, idx, pool, tbl).compile().as_text()
        return self._decode_text

    def decode_collective_counts(self, params) -> dict:
        from repro.analysis import hlo
        ops = hlo.parse_collectives(self.decode_hlo_text(params),
                                    self.n_devices)
        return dict(hlo.collective_counts(ops))


def make_paged_cells(cfg: ArchConfig, n_slots: int, cache_len: int,
                     block_size: int, n_pages: int, mesh=None,
                     buffer_depth: int = 2) -> PagedServeCells:
    """Build the paged engine's cells, single-device or sharded.

    ``n_pages`` counts *physical* pages (the allocator's blocks plus its
    trash page); ``buffer_depth`` is baked into the decode cell as the
    static pipelining knob of the paged-attention walk.
    """
    from repro.serve import paged

    paged.check_paged(cfg, cache_len, block_size,
                      tp_size=1 if mesh is None
                      else int(dict(mesh.shape).get("model", 1)))

    def serve_prefill(params, tokens):
        return registry.prefill(cfg, params, {"tokens": tokens},
                                cache_len=cache_len)

    def serve_decode(params, tokens, index, pool, tables):
        return paged.paged_decode_step(cfg, params, tokens, index, pool,
                                       tables, buffer_depth=buffer_depth)

    def serve_insert(pool, base_caches, table_row):
        return paged.insert_pages(cfg, pool, base_caches, table_row)

    if mesh is None:
        return PagedServeCells(
            cfg=cfg, n_slots=n_slots, cache_len=cache_len,
            block_size=block_size, n_pages=n_pages,
            buffer_depth=buffer_depth,
            prefill=jax.jit(serve_prefill),
            decode=jax.jit(serve_decode, donate_argnums=3),
            insert=jax.jit(serve_insert, donate_argnums=0))

    ctx = sharding.ShardingCtx(
        mesh, sharding.decode_rules("pod" in mesh.axis_names, False))
    pspec = sharding.param_shardings(registry.abstract_params(cfg), ctx)
    pool_shape = jax.eval_shape(
        lambda: paged.init_kv_pool(cfg, n_pages, block_size))
    poolspec = jax.tree_util.tree_map(
        lambda a: compat.named_sharding(mesh, sharding.safe_spec(
            a.shape, (None,) * (len(a.shape) - 2) + ("heads", None), ctx)),
        pool_shape)
    base_shape = registry.abstract_decode_caches(cfg, 1, cache_len)
    bspec = cache_shardings(base_shape, ctx)
    rep = compat.named_sharding(mesh, P())

    return PagedServeCells(
        cfg=cfg, n_slots=n_slots, cache_len=cache_len,
        block_size=block_size, n_pages=n_pages, buffer_depth=buffer_depth,
        prefill=jax.jit(_in_ctx(ctx, serve_prefill),
                        in_shardings=(pspec, rep),
                        out_shardings=(rep, bspec)),
        decode=jax.jit(_in_ctx(ctx, serve_decode),
                       in_shardings=(pspec, rep, rep, poolspec, rep),
                       out_shardings=(rep, poolspec), donate_argnums=3),
        insert=jax.jit(_in_ctx(ctx, serve_insert),
                       in_shardings=(poolspec, bspec, rep),
                       out_shardings=poolspec, donate_argnums=0),
        mesh=mesh, ctx=ctx, param_sharding=pspec, pool_sharding=poolspec)


def make_continuous_cells(cfg: ArchConfig, n_slots: int, cache_len: int,
                          mesh=None) -> ServeCells:
    """Build the continuous engine's cells, single-device or sharded.

    The sharded build uses the *batched* decode rules
    (``decode_rules(long_context=False)`` — heads/mlp/vocab and the KV
    sequence over 'model'), never the batch=1 long-context cell that
    ``_ctx_for`` would pick: the engine's slot axis is the batch.
    """
    def serve_prefill(params, tokens):
        return registry.prefill(cfg, params, {"tokens": tokens},
                                cache_len=cache_len)

    def _slot_decode(params, tokens, index, caches):
        return registry.decode_step(
            cfg, params, {"tokens": tokens, "index": index}, caches)

    def serve_decode(params, tokens, index, caches):
        return jax.vmap(_slot_decode, in_axes=(None, 0, 0, 0))(
            params, tokens, index, caches)

    def serve_insert(caches, slot_caches, slot):
        return jax.tree_util.tree_map(
            lambda c, p: jax.lax.dynamic_update_slice_in_dim(
                c, p[None].astype(c.dtype), slot, axis=0),
            caches, slot_caches)

    if mesh is None:
        return ServeCells(
            cfg=cfg, n_slots=n_slots, cache_len=cache_len,
            prefill=jax.jit(serve_prefill),
            decode=jax.jit(serve_decode, donate_argnums=3),
            insert=jax.jit(serve_insert, donate_argnums=0))

    ctx = sharding.ShardingCtx(
        mesh, sharding.decode_rules("pod" in mesh.axis_names, False))
    pspec = sharding.param_shardings(registry.abstract_params(cfg), ctx)
    base_shape = registry.abstract_decode_caches(cfg, 1, cache_len)
    bspec = cache_shardings(base_shape, ctx)
    slot_shape = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((n_slots,) + a.shape, a.dtype),
        base_shape)
    sspec = slot_cache_shardings(slot_shape, ctx)
    rep = compat.named_sharding(mesh, P())

    return ServeCells(
        cfg=cfg, n_slots=n_slots, cache_len=cache_len,
        prefill=jax.jit(_in_ctx(ctx, serve_prefill),
                        in_shardings=(pspec, rep),
                        out_shardings=(rep, bspec)),
        decode=jax.jit(_in_ctx(ctx, serve_decode),
                       in_shardings=(pspec, rep, rep, sspec),
                       out_shardings=(rep, sspec), donate_argnums=3),
        insert=jax.jit(_in_ctx(ctx, serve_insert),
                       in_shardings=(sspec, bspec, rep),
                       out_shardings=sspec, donate_argnums=0),
        mesh=mesh, ctx=ctx, param_sharding=pspec, slot_sharding=sspec)
