"""Paged continuous serving: the physical page pool may only change KV
*residency*, never tokens.

Fast in-process tier: a single-device paged engine (``paged=True``
routing cells through ``serve/step.make_paged_cells``) on a float32
smoke config must emit token streams bit-identical to the dense
engine's, fully recycle the page pool, and keep its allocator invariants
under ``debug=True`` (``kv.check()`` on every slot reset).  Unsupported
requests — a windowed/SSM arch, a cache the page size does not tile —
must be rejected at construction, not discovered mid-decode.

Subprocess tier (4 forced host devices, like ``test_serve_sharded``):
paged engines at tp=1/2/4 against the dense single-device engine on the
same seeded request set — token streams bit-identical across ALL
engines, scheduling decisions identical, pool recycled.  Float32 for the
same reason as the sharded differential: at f32 reduction-order noise
(~1e-7) sits far below greedy top-2 margins, so bit-identity is the
honest invariant; at bf16 a near-tied argmax could flip on a single ulp.
"""
import dataclasses
import os
import subprocess
import sys

import pytest


def _f32_smoke():
    from repro.configs import all_archs, smoke
    return dataclasses.replace(smoke(all_archs()["olmo-1b"]),
                               dtype="float32")


def test_paged_engine_matches_dense_single_device():
    import jax
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.loadgen import LoadSpec, make_requests
    cfg = _f32_smoke()
    params = registry.init_params(cfg, jax.random.key(0))
    spec = LoadSpec(n_requests=6, rate_rps=0.0, prompt_lens=(8, 16),
                    max_new_tokens=6, vocab_size=cfg.vocab_size, seed=3)

    def run(**kw):
        eng = ContinuousEngine(cfg, params, n_slots=4, cache_len=64,
                               block_size=8, **kw)
        reqs = eng.generate(make_requests(spec))
        eng.scheduler.check()
        assert eng.kv.n_free == eng.kv.n_blocks
        return eng, [list(r.generated) for r in reqs]

    dense_eng, dense = run()
    for depth in (1, 2):
        paged_eng, paged = run(paged=True, page_buffer_depth=depth,
                               debug=True)
        assert paged == dense, (depth, paged, dense)
        assert (list(paged_eng.scheduler.admit_log)
                == list(dense_eng.scheduler.admit_log))
        assert all(len(t) == 6 for t in paged)
        # after a full sweep every device table row is back to all-trash
        trash = paged_eng.kv.trash_page
        assert (paged_eng._tables_np == trash).all()


def test_paged_rejects_untileable_cache():
    import jax
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    cfg = _f32_smoke()
    params = registry.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="divisible by block_size"):
        ContinuousEngine(cfg, params, n_slots=2, cache_len=60,
                         block_size=8, paged=True)


@pytest.mark.parametrize("arch", ["h2o-danube-3-4b", "rwkv6-7b"])
def test_paged_rejects_unsupported_arch(arch):
    import jax
    from repro.configs import all_archs, smoke
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    cfg = smoke(all_archs()[arch])
    params = registry.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="keeps the dense path"):
        ContinuousEngine(cfg, params, n_slots=2, cache_len=64,
                         block_size=8, paged=True)


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax
import numpy as np
from repro.configs import all_archs, smoke
from repro.models import registry
from repro.serve.continuous import ContinuousEngine
from repro.serve.loadgen import LoadSpec, make_requests

cfg = dataclasses.replace(smoke(all_archs()["olmo-1b"]), dtype="float32")
params = registry.init_params(cfg, jax.random.key(0))
N_SLOTS, CACHE_LEN, BS, MAX_NEW = 4, 64, 8, 6

def run(tp, paged, depth=2):
    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS,
                           cache_len=CACHE_LEN, block_size=BS,
                           tp_size=tp, paged=paged,
                           page_buffer_depth=depth, debug=paged)
    spec = LoadSpec(n_requests=6, rate_rps=0.0, prompt_lens=(8, 16),
                    max_new_tokens=MAX_NEW, vocab_size=cfg.vocab_size,
                    seed=3)
    reqs = eng.generate(make_requests(spec))
    eng.scheduler.check()
    assert eng.kv.n_free == eng.kv.n_blocks, (tp, paged)
    if paged:
        assert (eng._tables_np == eng.kv.trash_page).all(), tp
    return eng, [list(r.generated) for r in reqs]

# dense single-device is the reference stream
_, dense = run(1, paged=False)
assert all(len(t) == MAX_NEW for t in dense)

# paged engines at every tensor-parallel width: bit-identical tokens —
# the pool (split over 'model' on the fused head axis at tp>1) and the
# page indirection change placement and residency, nothing else
engines = {}
for tp in (1, 2, 4):
    eng, paged_toks = run(tp, paged=True)
    engines[tp] = eng
    assert paged_toks == dense, (tp, paged_toks, dense)

# the paged pool really is sharded at tp>1: per-layer pool leaves split
# over the fused-head axis, tables/token scalars replicated
pool = engines[2]._pool
leaf = next(iter(pool.values()))
n_shards = {len(d.sharding.device_set) for d in pool.values()}
assert n_shards == {2}, n_shards
assert leaf.sharding.shard_shape(leaf.shape)[-2] == leaf.shape[-2] // 2

# buffer depth is a placement-free knob too
_, d4 = run(1, paged=True, depth=4)
assert d4 == dense

# the Pallas kernel (interpreted here) runs once per 'model' shard inside
# shard_map, since the compiler cannot partition a Mosaic call: same stream
from repro import runtime
with runtime.use_policy(paged_attention_impl="pallas"):
    _, kern = run(2, paged=True)
assert kern == dense, (kern, dense)

print("ALL_OK")
"""


def test_paged_engine_differential_4dev():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ALL_OK" in out.stdout, out.stdout + out.stderr
