"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("B,S,H,Kv,hd", [
    (2, 128, 4, 2, 64), (1, 256, 4, 4, 32), (2, 64, 8, 2, 16),
    (1, 128, 2, 1, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_matches_ref(B, S, H, Kv, hd, causal, window):
    ks = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Kv, hd), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("S,block_q,block_k,causal,window", [
    (130, 64, 64, True, 0),    # ragged tail past the last full block
    (100, 32, 64, True, 0),    # blocks of different sizes, both ragged
    (77, 32, 32, False, 0),    # non-causal: pad keys masked only by kpos<S
    (130, 64, 64, True, 48),   # sliding window across the ragged tail
])
def test_flash_attention_ragged_tail(S, block_q, block_k, causal, window):
    """Sequence lengths that do not tile the block grid: the kernel pads
    up, masks the pad keys (kpos < S) and slices the pad rows off — the
    fwd output must match the unpadded reference exactly (within fp32
    reduction noise)."""
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, S, 2, 16), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_k=block_k)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    assert got.shape == want.shape
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32).astype(dtype)
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert got.dtype == dtype
    assert jnp.max(jnp.abs(got.astype(jnp.float32)
                           - want.astype(jnp.float32))) < tol


@pytest.mark.parametrize("B,T,H,dh,chunk", [
    (2, 128, 2, 16, 32), (1, 64, 4, 32, 16), (2, 96, 1, 64, 32),
])
def test_rwkv6_scan_matches_ref(B, T, H, dh, chunk):
    ks = jax.random.split(jax.random.key(7), 6)
    r, k, v = [jax.random.normal(ks[i], (B, T, H, dh)) for i in range(3)]
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, dh))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, dh)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, dh, dh)) * 0.1
    y1, sT1 = ops.rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)
    y2, sT2 = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
    assert jnp.max(jnp.abs(y1 - y2)) < 1e-3
    assert jnp.max(jnp.abs(sT1 - sT2)) < 1e-3


def test_rwkv6_chunked_jnp_matches_ref():
    from repro.models.rwkv6 import wkv_chunked
    ks = jax.random.split(jax.random.key(3), 5)
    B, T, H, dh = 2, 128, 2, 16
    r, k, v = [jax.random.normal(ks[i], (B, T, H, dh)) for i in range(3)]
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, dh))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, dh)) * 0.3
    y1, s1 = wkv_chunked(r, k, v, w, u)
    y2, s2 = ref.rwkv6_scan_ref(r, k, v, w, u)
    assert jnp.max(jnp.abs(y1 - y2)) < 1e-3


def test_kernel_defaults_resolve_interpret_per_backend():
    """The kernel entry points default ``interpret=None`` and resolve per
    backend (the quant treatment, ROADMAP open item) — on this CPU
    container a default call runs the interpreter (a compiled-Mosaic
    attempt would fail), and the hardcoded ``interpret=True`` defaults
    are gone."""
    import inspect

    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import rwkv6_scan as rs_mod
    for fn in (fa_mod.flash_attention_fwd, rs_mod.rwkv6_scan_fwd):
        assert inspect.signature(fn).parameters["interpret"].default is None
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 1, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 1, 16), jnp.float32)
    got = fa_mod.flash_attention_fwd(q, k, v, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


def test_policy_flip_redispatches_without_stale_jit_cache(monkeypatch):
    """A ``runtime.policy()`` flip must change the kernel dispatch even for
    an already-seen shape: the jitted wrappers in ``kernels/ops.py`` key
    their cache on the resolved ``interpret`` (a static argument), so a
    flip retraces instead of silently reusing the first trace — the
    stale-cache hazard the quant wrappers always documented, fixed for
    attention/rwkv too."""
    from repro import runtime
    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import rwkv6_scan as rs_mod

    seen_fa, seen_rs = [], []
    real_fa, real_rs = fa_mod.flash_attention_fwd, rs_mod.rwkv6_scan_fwd
    monkeypatch.setattr(
        fa_mod, "flash_attention_fwd",
        lambda *a, **kw: seen_fa.append(kw["interpret"]) or real_fa(*a, **kw))
    monkeypatch.setattr(
        rs_mod, "rwkv6_scan_fwd",
        lambda *a, **kw: seen_rs.append(kw["interpret"]) or real_rs(*a, **kw))

    # odd shapes nothing else in the suite uses, so this test owns the
    # relevant jit-cache entries
    ks = jax.random.split(jax.random.key(13), 5)
    q = jax.random.normal(ks[0], (1, 96, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 96, 1, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 96, 1, 16), jnp.float32)
    r = jax.random.normal(ks[3], (1, 96, 1, 16), jnp.float32)
    w = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 96, 1, 16))) * 0.5 + 0.45
    u = jnp.zeros((1, 16))

    def trace_all():
        # abstract eval: records the trace-time dispatch without running
        # (a compiled-Mosaic attempt on CPU would otherwise fail)
        jax.eval_shape(lambda: ops.flash_attention(q, k, v, block_q=32,
                                                   block_k=32))
        jax.eval_shape(lambda: ops.rwkv6_scan(r, k, v, w, u, chunk=32))

    with runtime.use_policy(pallas_interpret=True):
        trace_all()
        trace_all()   # same shape + same policy: cache hit, no retrace
    with runtime.use_policy(pallas_interpret=False):
        trace_all()   # policy flip, same shape: MUST retrace, not reuse
    assert seen_fa == [True, False], seen_fa
    assert seen_rs == [True, False], seen_rs


def _paged_case(seed, S, H, Kv, hd, page_size, max_pages, lengths):
    """Random pool + per-sequence page tables (distinct pages, trash-padded
    rows for sequences that need fewer than max_pages)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_blocks = S * max_pages
    trash = n_blocks
    q = jnp.asarray(rng.standard_normal((S, H, hd)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((n_blocks + 1, page_size,
                                            2 * Kv, hd)), jnp.float32)
    perm = rng.permutation(n_blocks)
    tables = np.full((S, max_pages), trash, np.int32)
    k = 0
    for s, n in enumerate(lengths):
        need = -(-n // page_size)
        tables[s, :need] = perm[k:k + need]
        k += need
    return q, pool, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("S,H,Kv,hd,ps,max_pages,lengths", [
    (4, 4, 2, 16, 8, 6, (1, 13, 40, 48)),     # ragged incl. page-aligned
    (3, 8, 8, 32, 4, 8, (32, 7, 19)),         # MHA (rep=1), odd tails
    (2, 2, 1, 64, 16, 2, (16, 31)),           # single kv head, wide hd
    # 20 pages of 16 rows: lengths end inside, at and one past 128 rows
    # (the latent kernel's block), and past them
    (5, 4, 2, 16, 16, 20, (1, 127, 128, 129, 300)),
    # rows of 8 KiB (8 KV heads of 128, float32)
    (3, 8, 8, 128, 16, 6, (1, 16, 70)),
])
def test_paged_attention_kernel_matches_ref(depth, S, H, Kv, hd, ps,
                                            max_pages, lengths):
    """The Pallas decode kernel (interpret — the DMA pipeline runs under
    the interpreter on CPU) and its XLA twin both match the full-softmax
    oracle at every buffer depth, on ragged lengths with trash-padded
    tables."""
    from repro.kernels import paged_attention as pa
    q, pool, tables, lens = _paged_case(17, S, H, Kv, hd, ps, max_pages,
                                        lengths)
    want = ref.paged_attention_ref(q, pool, tables, lens)
    got_k = pa.paged_attention_fwd(q, pool, tables, lens,
                                   buffer_depth=depth, interpret=True)
    got_x = pa.paged_attention_xla(q, pool, tables, lens,
                                   buffer_depth=depth)
    assert jnp.max(jnp.abs(got_k - want)) < 2e-5
    assert jnp.max(jnp.abs(got_x - want)) < 2e-5


@pytest.mark.parametrize("poison", [1e6, float("nan")])
@pytest.mark.parametrize("H,Kv,hd,ps,max_pages,lengths", [
    (4, 2, 16, 8, 4, (5, 17, 26)),
    (4, 2, 16, 8, 40, (5, 170, 260)),       # long, ragged tables
    # a tensor-parallel shard's rows: 2 KV heads of 128 (mistral-nemo's 8
    # over 4 chips)
    (8, 2, 128, 16, 6, (5, 17, 70)),
], ids=["short", "long", "tp_shard"])
def test_paged_attention_ignores_trash_and_pad_positions(
        H, Kv, hd, ps, max_pages, lengths, poison):
    """Only the first ``length`` positions of a sequence's own pages may
    contribute: corrupting the trash page, the unowned pages and the
    owned-but-past-length tail — with a large value or NaN, which a zero
    probability does not cancel — must not move the output."""
    import numpy as np
    from repro.kernels import paged_attention as pa
    q, pool, tables, lens = _paged_case(23, 3, H, Kv, hd, ps, max_pages,
                                        lengths)
    base = pa.paged_attention_fwd(q, pool, tables, lens, buffer_depth=2,
                                  interpret=True)
    owned = set()
    tbl = np.asarray(tables)
    for s, n in enumerate(lengths):
        owned.update(tbl[s, :-(-n // ps)].tolist())
    poisoned = np.array(pool)
    for p in range(poisoned.shape[0]):
        if p not in owned:
            poisoned[p] = poison         # trash + unowned pages
    for s, n in enumerate(lengths):
        last = tbl[s, (n - 1) // ps]
        poisoned[last, n % ps or ps:] = poison   # past-length tail
    got = pa.paged_attention_fwd(q, jnp.asarray(poisoned), tables, lens,
                                 buffer_depth=2, interpret=True)
    assert jnp.max(jnp.abs(got - base)) == 0.0


def test_paged_attention_bf16_operands_match_f32_oracle():
    """bf16 queries against bf16 pages over long ragged tables: the
    kernel takes each page to float32 before it splits K and V, and
    returns float32, so it matches the float32 oracle on the same bf16
    values."""
    from repro.kernels import paged_attention as pa
    lengths = (1, 127, 128, 129, 300)
    q, pool, tables, lens = _paged_case(19, 5, 4, 2, 16, 16, 20, lengths)
    q, pool = q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)
    want = ref.paged_attention_ref(q.astype(jnp.float32),
                                   pool.astype(jnp.float32), tables, lens)
    got = pa.paged_attention_fwd(q, pool, tables, lens, buffer_depth=2,
                                 interpret=True)
    assert got.dtype == jnp.float32
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("page_size,row,max_pages,pages", [
    (16, (640,), 256, 8),       # Moonlight's latent pages: 128 rows
    (8, (640,), 256, 16),
    (16, (640,), 5, 5),         # a table shorter than a block
    (16, (16, 128), 256, 8),    # mistral-nemo's K/V pages (8 KV heads)
    (16, (32, 128), 128, 8),    # olmo-1b's (16 heads): 1 MiB
    (16, (64, 128), 256, 4),    # wider rows: as many pages as VMEM holds
    (16, (512, 128), 256, 1),   # a page past the budget: still one
])
def test_pages_per_block_from_the_page_shape(page_size, row, max_pages,
                                             pages):
    """The block rule, from a page's shape alone: the fewest whole pages
    that give 128 rows, never more than the table holds nor than
    ``MAX_BLOCK_BYTES`` of VMEM holds, and at least one."""
    import math
    from repro.kernels import paged_attention as pa
    row_bytes = math.prod(row) * 2                       # bf16
    n = pa.pages_per_block(page_size, row_bytes, max_pages)
    assert n == pages
    assert n <= max_pages
    assert n == 1 or n * page_size * row_bytes <= pa.MAX_BLOCK_BYTES


def _ring(fwd, *args):
    """The VMEM ring of ``fwd``'s page walk, (depth, pages a block,
    page_size, *row), read off the traced ``pallas_call``."""
    (eqn,) = [e for e in jax.make_jaxpr(fwd)(*args).jaxpr.eqns
              if e.primitive.name == "pallas_call"]
    return [v.aval.shape for v in eqn.params["jaxpr"].invars
            if str(v.aval.memory_space) == "vmem"][0]


@pytest.mark.parametrize("kind,row,max_pages,pages", [
    ("kv", (4, 128), 256, 1),    # a tensor-parallel shard's K/V rows
    ("kv", (16, 128), 256, 1),   # mistral-nemo's K/V rows
    ("kv", (32, 128), 128, 1),   # olmo-1b's
    ("latent", (640,), 256, 8),  # Moonlight's latent rows: 128 a block
    ("latent", (640,), 5, 5),
])
def test_paged_kernels_walk_blocks_of_their_own_size(kind, row, max_pages,
                                                     pages):
    """The K/V kernel walks a page a block whatever its rows' width (it is
    bound per row, and a block is contracted whole); the latent kernel
    walks the blocks ``pages_per_block`` gives its page shape."""
    from repro.kernels import paged_attention as pa
    from repro.kernels import paged_mla_attention as pm
    S, ps = 2, 16
    sds = jax.ShapeDtypeStruct
    tables = sds((S, max_pages), jnp.int32)
    lens = sds((S,), jnp.int32)
    pool = sds((3, S * max_pages + 1, ps) + row, jnp.bfloat16)
    if kind == "kv":
        q = sds((S, row[0], row[1]), jnp.bfloat16)
        fwd = functools.partial(pa.paged_attention_fwd, interpret=True)
    else:
        q = sds((S, 16, row[0]), jnp.bfloat16)
        fwd = functools.partial(pm.paged_mla_attention_fwd, latent=512,
                                sm_scale=0.1, interpret=True)
    assert _ring(fwd, q, pool, tables, lens)[1:] == (pages, ps) + row


def _stacked_case(seed, L, *args):
    """``_paged_case`` with an (L, n_pages, ...) pool: L layers of pages
    drawn apart, one block table shared by every layer."""
    q, pool, tables, lens = _paged_case(seed, *args)
    layers = [pool] + [jax.random.normal(jax.random.key(seed + i), pool.shape)
                       for i in range(1, L)]
    return q, jnp.stack(layers), tables, lens


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_attention_stacked_pool_matches_ref(layer):
    """Handed a stacked 3-layer pool and a layer index (traced, as the
    decode step's scan passes it), the kernel (interpret) and the XLA
    twin attend that layer's pages where they lie: both match the oracle
    over ``pool[layer]``."""
    from repro.kernels import paged_attention as pa
    q, pool, tables, lens = _stacked_case(31, 3, 4, 4, 2, 16, 8, 6,
                                          (1, 13, 40, 48))
    want = ref.paged_attention_ref(q, pool[layer], tables, lens)
    got_k = jax.jit(lambda l: pa.paged_attention_fwd(
        q, pool, tables, lens, l, buffer_depth=2, interpret=True))(layer)
    got_x = jax.jit(lambda l: pa.paged_attention_xla(
        q, pool, tables, lens, l, buffer_depth=2))(layer)
    assert jnp.max(jnp.abs(got_k - want)) < 2e-5
    assert jnp.max(jnp.abs(got_x - want)) < 2e-5


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_attention_stacked_ignores_other_layers(layer):
    """The trash page, the unowned pages and the past-length tails of the
    attended layer, and every page of the other layers, poisoned: the
    kernel's output over the stacked pool at ``layer`` equals its output
    over that layer alone, clean."""
    import numpy as np
    from repro.kernels import paged_attention as pa
    lengths = (5, 17, 26)
    q, pool, tables, lens = _stacked_case(37, 3, 3, 4, 2, 16, 8, 4, lengths)
    base = pa.paged_attention_fwd(q, pool[layer], tables, lens,
                                  buffer_depth=2, interpret=True)
    tbl = np.asarray(tables)
    owned = set()
    for s, n in enumerate(lengths):
        owned.update(tbl[s, :-(-n // 8)].tolist())
    poisoned = np.full(pool.shape, 1e6, np.float32)
    poisoned[layer, sorted(owned)] = np.asarray(pool[layer])[sorted(owned)]
    for s, n in enumerate(lengths):
        last = tbl[s, (n - 1) // 8]
        poisoned[layer, last, n % 8 or 8:] = 1e6
    got = pa.paged_attention_fwd(q, jnp.asarray(poisoned), tables, lens,
                                 layer, buffer_depth=2, interpret=True)
    assert jnp.max(jnp.abs(got - base)) == 0.0


def test_paged_attention_policy_dispatch(monkeypatch):
    """``ops.paged_attention`` routes per policy without a stale jit
    cache: ``pallas`` forces the kernel, ``xla`` the twin, ``auto`` keys
    on the backend (the twin on this CPU container), and the
    ``paged_buffer_depth`` knob reaches the dispatch as a static."""
    from repro import runtime
    from repro.kernels import paged_attention as pa_mod

    seen = []
    real = pa_mod.paged_attention_fwd
    monkeypatch.setattr(
        pa_mod, "paged_attention_fwd",
        lambda *a, **kw: seen.append(kw["buffer_depth"]) or real(*a, **kw))
    q, pool, tables, lens = _paged_case(29, 2, 2, 1, 16, 4, 3, (3, 11))

    assert not ops.use_paged_kernel()          # auto on CPU: the XLA twin
    with runtime.use_policy(paged_attention_impl="xla"):
        assert not ops.use_paged_kernel()
    with runtime.use_policy(paged_attention_impl="pallas"):
        assert ops.use_paged_kernel()
        jax.eval_shape(lambda: ops.paged_attention(q, pool, tables, lens))
        jax.eval_shape(lambda: ops.paged_attention(q, pool, tables, lens))
        with runtime.use_policy(paged_buffer_depth=3):
            jax.eval_shape(lambda: ops.paged_attention(q, pool, tables,
                                                       lens))
    assert seen == [2, 3], seen                # depth flip retraced; the
    #                                            repeat call was a cache hit
    got = ops.paged_attention(q, pool, tables, lens)   # auto path runs
    want = ref.paged_attention_ref(q, pool, tables, lens)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


# the last two rows are too long for one VMEM tile: column-tiled passes
@pytest.mark.parametrize("N,C", [(256, 512), (512, 1024), (128, 64),
                                 (4, 1 << 20), (3, 600_001)])
def test_quant_kernel_matches_ref(N, C):
    from repro import runtime
    x = jax.random.normal(jax.random.key(5), (N, C)) * 3
    with runtime.use_policy(quant_impl="pallas"):
        q1, s1 = ops.quantize_int8(x)
        xd = ops.dequantize_int8(q1, s1)
    q2, s2 = ref.quantize_int8_ref(x)
    assert (q1 == q2).all() and jnp.allclose(s1, s2)
    assert jnp.max(jnp.abs(xd - x)) <= float(jnp.max(s1)) + 1e-6


def _mla_case(seed, L, S, H, W, latent, ps, max_pages, lengths):
    """A stacked latent pool (L, n_pages, ps, W) with trash-padded tables
    over ragged lengths, and absorbed queries (S, H, W)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_blocks = S * max_pages
    q = jnp.asarray(rng.standard_normal((S, H, W)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((L, n_blocks + 1, ps, W)),
                       jnp.float32)
    perm = rng.permutation(n_blocks)
    tables = np.full((S, max_pages), n_blocks, np.int32)
    k = 0
    for s, n in enumerate(lengths):
        need = -(-n // ps)
        tables[s, :need] = perm[k:k + need]
        k += need
    return q, pool, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def _mla_oracle(q, pool, tables, lengths, latent, scale):
    """Full softmax over each sequence's gathered rows."""
    import numpy as np
    out = []
    for s in range(q.shape[0]):
        n = int(lengths[s])
        rows = pool[np.asarray(tables[s])].reshape(-1, pool.shape[-1])[:n]
        p = jax.nn.softmax(q[s] @ rows.T * scale, -1)
        out.append(p @ rows[:, :latent])
    return jnp.stack(out)


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("ps,max_pages,lengths", [
    (8, 6, (1, 13, 40, 48)),                    # one block: the table
    (16, 20, (1, 127, 128, 129, 300)),          # three 128-row blocks
], ids=["block", "blocks"])
def test_paged_mla_attention_kernel_matches_twin_and_oracle(
        ps, max_pages, lengths, layer, depth):
    """The latent kernel (interpret) and its XLA twin, handed a stacked
    pool and a traced layer index, match a full softmax over that layer's
    rows — scores against the whole row, output over its latent prefix —
    on ragged lengths (page- and block-aligned ones among them, and ones
    a row past a block) with trash-padded tables."""
    from repro.kernels import paged_mla_attention as pm
    S = len(lengths)
    q, pool, tables, lens = _mla_case(41, 3, S, 4, 128, 40, ps, max_pages,
                                      lengths)
    scale = 24 ** -0.5
    want = _mla_oracle(q, pool[layer], tables, lens, 40, scale)
    got_k = jax.jit(lambda l: pm.paged_mla_attention_fwd(
        q, pool, tables, lens, l, latent=40, sm_scale=scale,
        buffer_depth=depth, interpret=True))(layer)
    got_x = jax.jit(lambda l: pm.paged_mla_attention_xla(
        q, pool, tables, lens, l, latent=40, sm_scale=scale,
        buffer_depth=depth))(layer)
    assert got_k.shape == (S, 4, 40)
    assert jnp.max(jnp.abs(got_k - want)) < 2e-5
    assert jnp.max(jnp.abs(got_x - got_k)) < 2e-5


def test_paged_mla_attention_bf16_scores_are_exact():
    """bf16 queries against bf16 latent rows, over several blocks: scores
    of the stored operands accumulated in float32 match the float32
    oracle on the same bf16 values (the kernel returns float32)."""
    from repro.kernels import paged_mla_attention as pm
    lengths = (1, 127, 128, 129, 300)
    q, pool, tables, lens = _mla_case(47, 2, 5, 4, 128, 40, 16, 20, lengths)
    q, pool = q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16)
    want = _mla_oracle(q.astype(jnp.float32), pool[1].astype(jnp.float32),
                       tables, lens, 40, 0.2)
    got = pm.paged_mla_attention_fwd(q, pool, tables, lens, 1, latent=40,
                                     sm_scale=0.2, buffer_depth=2,
                                     interpret=True)
    assert got.dtype == jnp.float32
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("poison", [1e6, float("nan")])
@pytest.mark.parametrize("ps,max_pages,lengths", [
    (8, 4, (5, 17, 26)),                    # one block: the whole table
    (8, 40, (5, 170, 260)),                 # blocks of 16 pages, ragged
], ids=["block", "blocks"])
def test_paged_mla_attention_ignores_trash_and_pad_positions(
        ps, max_pages, lengths, poison):
    """The trash page, unowned pages, past-length tails and the other
    layer poisoned, with a large value or NaN: the kernel's output does
    not move."""
    import numpy as np
    from repro.kernels import paged_mla_attention as pm
    q, pool, tables, lens = _mla_case(43, 2, 3, 4, 128, 40, ps, max_pages,
                                      lengths)
    kw = dict(latent=40, sm_scale=0.2, buffer_depth=2, interpret=True)
    base = pm.paged_mla_attention_fwd(q, pool, tables, lens, 1, **kw)
    tbl = np.asarray(tables)
    owned = {int(p) for s, n in enumerate(lengths)
             for p in tbl[s, :-(-n // ps)]}
    poisoned = np.array(pool)
    poisoned[0] = poison                               # the other layer
    for p in range(poisoned.shape[1]):
        if p not in owned:
            poisoned[1, p] = poison                    # trash + unowned
    for s, n in enumerate(lengths):
        poisoned[1, tbl[s, (n - 1) // ps], n % ps or ps:] = poison
    got = pm.paged_mla_attention_fwd(q, jnp.asarray(poisoned), tables, lens,
                                     1, **kw)
    assert jnp.max(jnp.abs(got - base)) == 0.0


@pytest.mark.parametrize("sizes", [
    (3, 0, 5, 1, 0, 7, 2, 0),          # empty experts, groups across tiles
    (16, 16, 0, 0, 0, 0, 0, 0),        # tile-aligned groups
    (0, 0, 0, 0, 0, 0, 0, 29),         # one expert takes every row
])
@pytest.mark.parametrize("layer", [None, 1])
def test_expert_gmm_matches_per_expert_loop(sizes, layer):
    """The grouped matmul (interpret) and its XLA twin against a plain
    loop over experts, each multiplying its own rows, on weights stacked
    over layers (read at a traced index) or one layer's; rows past the
    groups (here 3 more) are the caller's to mask."""
    import numpy as np
    from repro.kernels import expert_gmm as eg
    rng = np.random.default_rng(7)
    E, k, n = len(sizes), 64, 96
    m = sum(sizes) + 3
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((2, E, k, n)), jnp.float32)
    w = rhs if layer is not None else rhs[0]
    gs = jnp.asarray(sizes, jnp.int32)
    want, start = np.zeros((sum(sizes), n), np.float32), 0
    for e, size in enumerate(sizes):
        want[start:start + size] = np.asarray(
            lhs[start:start + size] @ rhs[layer or 0, e])
        start += size
    if layer is None:
        got_k = eg.expert_gmm_fwd(lhs, w, gs, interpret=True)
        got_x = eg.expert_gmm_xla(lhs, w, gs)
    else:
        got_k = jax.jit(lambda l: eg.expert_gmm_fwd(
            lhs, w, gs, l, interpret=True))(layer)
        got_x = jax.jit(lambda l: eg.expert_gmm_xla(lhs, w, gs, l))(layer)
    assert got_k.shape == (m, n)
    assert jnp.max(jnp.abs(got_k[:sum(sizes)] - want)) < 1e-4
    assert jnp.max(jnp.abs(got_x[:sum(sizes)] - want)) < 1e-4


def test_expert_gmm_tiles():
    """Decode's few rows take 16-row tiles, a prefill's many 128; the n
    tile is the widest whose weight block fits 8 MiB (the whole expert at
    Moonlight's widths)."""
    from repro.kernels import expert_gmm as eg
    assert eg.row_tile(192) == 16 and eg.row_tile(3072) == 128
    assert eg.col_tile(2048, 1408, 2) == 1408
    assert eg.col_tile(1408, 2048, 2) == 2048
    assert eg.col_tile(4096, 4096, 2) == 1024
