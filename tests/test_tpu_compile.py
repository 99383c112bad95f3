"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler ships with jaxlib, so a chip that is described (not
attached) can compile them here: Mosaic refuses what interpret mode
accepts (a primitive with no TPU lowering, an unaligned slice, too much
VMEM), and that refusal costs no chip time.  Each test passes
``interpret=False`` and asserts the compiled text holds the kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every pytest-xdist
worker imports this file.  Where it cannot be described the fixture skips.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.configs import all_archs
from repro.kernels import expert_gmm as eg
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import paged_mla_attention as pm
from repro.kernels import quant
from repro.kernels import rwkv6_scan as rs

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out while these run
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_paged_attention_compiles_at_olmo_widths(one_chip):
    # olmo-1b: 16 MHA heads of 128; 8 slots x 1024 positions in 16-token
    # pages, plus the trash page
    q = _sds(one_chip, (8, 16, 128), jnp.bfloat16)
    pool = _sds(one_chip, (513, 16, 32, 128), jnp.bfloat16)
    tables = _sds(one_chip, (8, 64), jnp.int32)
    lengths = _sds(one_chip, (8,), jnp.int32)
    text = _compiled_text(
        lambda *a: pa.paged_attention_fwd(*a, interpret=False),
        q, pool, tables, lengths)
    assert KERNEL in text


def test_paged_mla_attention_compiles_at_moonlight_widths(one_chip):
    # Moonlight-16B-A3B: 16 heads over 512 + 64 latent rows padded to 640
    # lanes; 32 slots x 4096 positions in 16-token pages, 8 layers stacked
    q = _sds(one_chip, (32, 16, 640), jnp.bfloat16)
    pool = _sds(one_chip, (8, 8193, 16, 640), jnp.bfloat16)
    tables = _sds(one_chip, (32, 256), jnp.int32)
    lengths = _sds(one_chip, (32,), jnp.int32)
    layer = _sds(one_chip, (), jnp.int32)
    text = _compiled_text(
        lambda *a: pm.paged_mla_attention_fwd(
            *a, latent=512, sm_scale=192 ** -0.5, interpret=False),
        q, pool, tables, lengths, layer)
    assert KERNEL in text


# (rows, k, n): a 32-slot decode step's 32 x 6 token-expert rows and a
# 512-token prefill's, through the gate/up (2048 -> 1408) and down
# (1408 -> 2048) projections of Moonlight's 64 experts, stacked over 8
# layers and read at a traced layer
@pytest.mark.parametrize("m,k,n", [(192, 2048, 1408), (192, 1408, 2048),
                                   (3072, 2048, 1408), (3072, 1408, 2048)])
def test_expert_gmm_compiles_at_moonlight_widths(one_chip, m, k, n):
    text = _compiled_text(
        lambda a, b, g, l: eg.expert_gmm_fwd(a, b, g, l, interpret=False),
        _sds(one_chip, (m, k), jnp.bfloat16),
        _sds(one_chip, (8, 64, k, n), jnp.bfloat16),
        _sds(one_chip, (64,), jnp.int32), _sds(one_chip, (), jnp.int32))
    assert KERNEL in text


def test_flash_attention_compiles_at_olmo_widths(one_chip):
    x = _sds(one_chip, (1, 512, 16, 128), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, interpret=False),
        x, x, x)
    assert KERNEL in text


# (4096, 2048): one (2*d_model x d_model) gradient slab of olmo-1b's
# widths.  (4, 1 << 20): a 16 MiB gradient bucket chunked over 4 devices,
# whose rows only fit VMEM as column tiles.
@pytest.mark.parametrize("shape", [(4096, 2048), (4, 1 << 20)])
@pytest.mark.parametrize("op", ["quantize", "dequantize"])
def test_int8_quant_compiles_at_olmo_widths(one_chip, op, shape):
    if op == "quantize":
        text = _compiled_text(
            lambda x: quant.quantize_int8(x, interpret=False),
            _sds(one_chip, shape, jnp.float32))
    else:
        text = _compiled_text(
            lambda q, s: quant.dequantize_int8(q, s, interpret=False),
            _sds(one_chip, shape, jnp.int8),
            _sds(one_chip, (shape[0], 1), jnp.float32))
    assert KERNEL in text


def test_rwkv6_scan_compiles_at_rwkv6_7b_widths(one_chip):
    cfg = all_archs()["rwkv6-7b"]
    H, dh = cfg.num_heads, cfg.rwkv_head_dim
    x = _sds(one_chip, (1, 256, H, dh), jnp.float32)
    u = _sds(one_chip, (H, dh), jnp.float32)
    text = _compiled_text(
        lambda r, k, v, w, u: rs.rwkv6_scan_fwd(r, k, v, w, u,
                                                interpret=False),
        x, x, x, x, u)
    assert KERNEL in text


def _pool_passes(text: str, page_dims: tuple) -> list[str]:
    """Copies and dynamic slices in compiled HLO ``text`` whose result
    ends in ``page_dims``: for the pool's page geometry ``(n_pages, bs,
    *row)``, the whole stacked pool, one layer of it, or one layer with a
    unit axis; for an expert kernel's ``(E, k, n)``, one layer's experts
    copied out of the stacked weights."""
    op = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\(")
    hits = []
    for line in text.splitlines():
        m = op.match(line)
        if not m or not re.search(r"copy|dynamic[-_]slice",
                                  m.group(1) + " " + m.group(3)):
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims[-len(page_dims):] == page_dims:
            hits.append(line.strip()[:160])
    return hits


# (arch, layers kept, slots, positions, peak bound): chip_smoke.py's
# engine, and the benchmark's cells (perfbench/configs): olmo-1b at the
# published context, one 8-layer pipeline stage of mistral-nemo-12b and
# the first 9-layer stage of Moonlight-16B-A3B (latent pages, 64 experts
# a layer).  Params + pool + temps fit the 16 GB chip.
@pytest.mark.parametrize("arch,layers,n_slots,cache_len,peak", [
    ("olmo-1b", None, 8, 1024, 8e9),
    ("olmo-1b", None, 16, 2048, 8e9),
    ("mistral-nemo-12b", 8, 16, 4096, 12e9),
    ("moonlight-16b-a3b", 9, 32, 4096, 14.5e9),
], ids=["olmo-1b-8x1024", "olmo-1b-16x2048", "mistral-nemo-12b-s8-16x4096",
        "moonlight-16b-a3b-s9-32x4096"])
def test_paged_decode_cell_compiles_with_kernel(one_chip, arch, layers,
                                                n_slots, cache_len, peak):
    """The engine's decode program at published widths, 16-token pages.
    On this CPU the policy's auto dispatch would pick the XLA twin, so the
    test steers it to the compiled kernel.  The pool rides the layer scan
    in place: the program's scratch stays under one layer's share of the
    pool, and no copy or slice of the pool (or of one layer of it) is
    left."""
    from repro.models import registry
    from repro.serve import paged
    from repro.serve.step import make_paged_cells

    cfg = all_archs()[arch]
    if layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    block = 16
    n_pages = n_slots * cache_len // block + 1

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    with runtime.use_policy(paged_attention_impl="pallas",
                            pallas_interpret=False):
        cells = make_paged_cells(cfg, n_slots, cache_len, block, n_pages)
        compiled = cells.decode.lower(
            place(registry.abstract_params(cfg)),
            _sds(one_chip, (n_slots, 1), jnp.int32),
            _sds(one_chip, (n_slots,), jnp.int32),
            place(jax.eval_shape(cells.init_pool)),
            _sds(one_chip, (n_slots, cells.max_pages), jnp.int32),
        ).compile()
    text = compiled.as_text()
    assert KERNEL in text
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes < peak
    pool_bytes = paged.pool_geometry(cfg, n_pages, block)["pool_bytes"]
    assert mem.temp_size_in_bytes < pool_bytes / cfg.num_groups(), \
        (mem.temp_size_in_bytes, pool_bytes)
    page_dims = jax.eval_shape(cells.init_pool)["l0"].shape[1:]
    assert _pool_passes(text, page_dims) == []
    if cfg.num_experts:
        # the latent kernel and the grouped matmul run, named, and read
        # each layer's experts in place
        assert "%paged_mla_attention" in text and "%expert_gmm" in text
        D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
        assert _pool_passes(text, (E, D, F)) == []
        assert _pool_passes(text, (E, F, D)) == []
