"""Serving steps: prefill and single-token decode, fully sharded.

decode cells: the KV cache is sequence-split over 'model' (flash-decode
style) for normal batched decode, and over every mesh axis for the
batch=1 long_500k cell (see parallel/sharding.decode_rules).

``make_cells`` packages the three programs the continuous-batching
engine drives (batch-1 prefill, the all-slot decode step, insertion of
a prefill into the state) as one :class:`ServeCells`, and picks where
the KV state lives: the page pool of ``serve/paged.py`` for every config
it can hold, per-slot caches for the rest.  The cells are either
single-device or tensor-parallel over a ``("data", "model")`` mesh with
explicit in/out shardings, so the compiled steps are reshard-free at the
call boundary and a silent resharding shows up as a collective-count
mismatch (guarded in ``tests/test_serve_sharded.py``).
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
from repro.serve.kv import blocks_for


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
    """The continuous engine's three compiled programs + their placements.

    The KV state lives where ``make_cells`` put it (``paged``): in the
    physical page pool of ``serve/paged.py``, one leaf per layer with the
    slot dimension in the block *tables*, or in per-slot caches stacked
    along a leading slot axis.  Either way the engine drives the same
    calling convention: ``decode(params, tok (S, 1), idx (S,), state,
    tables (S, max_pages))`` returns ``(logits (S, 1, V), state)``, plus
    the experts' load with routed experts; ``insert(state, base caches,
    *insert_args(slot, table_row))`` returns the state.  ``mesh=None`` is
    the single-device build: the placements are identities.  With a mesh
    the programs carry explicit in/out shardings (params by the decode
    rules, the state split over 'model', tokens/positions/tables
    replicated) and the placement helpers ``device_put`` accordingly.
    """
    cfg: ArchConfig
    n_slots: int
    cache_len: int
    block_size: int
    n_pages: int
    buffer_depth: int
    paged: bool
    geometry: dict           # the state's footprint (pool_geometry's keys)
    init: Callable           # () -> state, unplaced
    prefill: Callable        # (params, tokens[1,P]) -> (logits, base caches)
    decode: Callable         # (params, tok[S,1], idx[S], state, tables[S,mp])
    #                          -> (logits, state[, expert load])
    insert: Callable         # (state, base caches, *insert_args) -> state
    mesh: Optional[object] = None
    ctx: Optional[sharding.ShardingCtx] = None
    param_sharding: Optional[object] = None     # pytree of NamedSharding
    state_sharding: Optional[object] = None     # state pytree of NamedSharding
    _decode_text: Optional[str] = field(default=None, repr=False)

    @property
    def max_pages(self) -> int:
        """Width of a device table row: the pages of a whole cache."""
        return blocks_for(self.cache_len, self.block_size)

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

    def init_state(self):
        state = self.init()
        if self.state_sharding is None:
            return state
        return jax.device_put(state, self.state_sharding)

    def insert_args(self, slot: int, table_row) -> tuple:
        """What ``insert`` takes after the state and the prefill caches:
        the request's page ids, or its slot."""
        return (jnp.asarray(table_row),) if self.paged else (jnp.int32(slot),)

    # -- HLO inspection (tests + the sharded-sweep experiment) -------------

    def decode_hlo_text(self, params) -> str:
        """Compiled HLO of the decode program (cached; abstract args, so
        this never touches — or donates — live buffers)."""
        if self._decode_text is None:
            p = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            tok = jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32)
            idx = jax.ShapeDtypeStruct((self.n_slots,), jnp.int32)
            state = jax.eval_shape(self.init)
            tbl = jax.ShapeDtypeStruct((self.n_slots, self.max_pages),
                                       jnp.int32)
            self._decode_text = self.decode.lower(
                p, tok, idx, state, tbl).compile().as_text()
        return self._decode_text

    def decode_collective_counts(self, params) -> dict:
        """Trip-count-weighted per-kind collective counts of the compiled
        decode step — the silent-resharding guard: an implicit resharding
        XLA inserts at the call boundary changes these."""
        from repro.analysis import hlo
        ops = hlo.parse_collectives(self.decode_hlo_text(params),
                                    self.n_devices)
        return dict(hlo.collective_counts(ops))


def make_cells(cfg: ArchConfig, n_slots: int, cache_len: int,
               block_size: int, n_pages: int, mesh=None,
               buffer_depth: int = 2) -> ServeCells:
    """Build the continuous engine's cells, single-device or sharded.

    The one place that picks where the KV state lives: the page pool of
    ``serve/paged.py`` for every config it can hold
    (``paged.paged_supported``), per-slot caches for the rest (windowed
    attention, recurrent and hybrid states).  A pageable config is held
    to the pool's terms (``paged.check_paged``: pages tile ``cache_len``,
    latent attention on one device); it never falls back.

    ``n_pages`` counts *physical* pages (the allocator's blocks plus its
    trash page); ``buffer_depth`` is baked into the paged decode program
    as the static pipelining knob of the page walk.  The sharded build
    uses the *batched* decode rules (``decode_rules(long_context=False)``:
    heads/mlp/vocab, and the per-slot KV sequence, over 'model'), never
    the batch=1 long-context cell ``_ctx_for`` would pick: the engine's
    slot axis is the batch.
    """
    from repro.serve import paged as paged_kv

    is_paged = paged_kv.paged_supported(cfg)

    def serve_prefill(params, tokens):
        return registry.prefill(cfg, params, {"tokens": tokens},
                                cache_len=cache_len)

    if is_paged:
        paged_kv.check_paged(cfg, cache_len, block_size,
                             tp_size=1 if mesh is None
                             else int(dict(mesh.shape).get("model", 1)))
        geometry = paged_kv.pool_geometry(cfg, n_pages, block_size)

        def init():
            return paged_kv.init_kv_pool(cfg, n_pages, block_size)

        def serve_decode(params, tokens, index, pool, tables):
            return paged_kv.paged_decode_step(cfg, params, tokens, index,
                                              pool, tables,
                                              buffer_depth=buffer_depth)

        def serve_insert(pool, base_caches, table_row):
            return paged_kv.insert_pages(cfg, pool, base_caches, table_row)
    else:
        def init():
            base = registry.init_decode_caches(cfg, 1, cache_len)
            return jax.tree_util.tree_map(
                lambda a: jnp.stack([a] * n_slots), base)

        # a "page" here is one allocator block's share of a slot's cache
        state_bytes = sum(a.size * a.dtype.itemsize for a in
                          jax.tree_util.tree_leaves(jax.eval_shape(init)))
        geometry = {"n_pages": n_pages, "block_size": block_size,
                    "page_bytes": state_bytes // (
                        n_slots * blocks_for(cache_len, block_size)),
                    "pool_bytes": state_bytes, "state_kind": "slot"}

        def _slot_decode(params, tokens, index, caches):
            return registry.decode_step(
                cfg, params, {"tokens": tokens, "index": index}, caches)

        def serve_decode(params, tokens, index, caches, tables):
            # tables: the paged calling convention's, unread here
            logits, caches = jax.vmap(_slot_decode, in_axes=(None, 0, 0, 0))(
                params, tokens[:, :, None], index, caches)
            return logits[:, 0], caches

        def serve_insert(caches, base_caches, slot):
            return jax.tree_util.tree_map(
                lambda c, p: jax.lax.dynamic_update_slice_in_dim(
                    c, p[None].astype(c.dtype), slot, axis=0),
                caches, base_caches)

    shared = dict(cfg=cfg, n_slots=n_slots, cache_len=cache_len,
                  block_size=block_size, n_pages=n_pages,
                  buffer_depth=buffer_depth, paged=is_paged,
                  geometry=geometry, init=init)
    if mesh is None:
        return ServeCells(
            **shared, prefill=jax.jit(serve_prefill),
            decode=jax.jit(serve_decode, donate_argnums=3),
            insert=jax.jit(serve_insert, donate_argnums=0))

    ctx = sharding.ShardingCtx(
        mesh, sharding.decode_rules("pod" in mesh.axis_names, False))
    pspec = sharding.param_shardings(registry.abstract_params(cfg), ctx)
    base_shape = registry.abstract_decode_caches(cfg, 1, cache_len)
    bspec = cache_shardings(base_shape, ctx)
    state_shape = jax.eval_shape(init)
    if is_paged:
        sspec = jax.tree_util.tree_map(
            lambda a: compat.named_sharding(mesh, sharding.safe_spec(
                a.shape, (None,) * (len(a.shape) - 2) + ("heads", None),
                ctx)),
            state_shape)
    else:
        sspec = slot_cache_shardings(state_shape, ctx)
    rep = compat.named_sharding(mesh, P())

    return ServeCells(
        **shared,
        prefill=jax.jit(_in_ctx(ctx, serve_prefill),
                        in_shardings=(pspec, rep),
                        out_shardings=(rep, bspec)),
        decode=jax.jit(_in_ctx(ctx, serve_decode),
                       in_shardings=(pspec, rep, rep, sspec, rep),
                       out_shardings=(rep, sspec), donate_argnums=3),
        insert=jax.jit(_in_ctx(ctx, serve_insert),
                       in_shardings=(sspec, bspec, rep),
                       out_shardings=sspec, donate_argnums=0),
        mesh=mesh, ctx=ctx, param_sharding=pspec, state_sharding=sspec)
