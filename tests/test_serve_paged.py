"""Paged continuous serving: the engine keeps a pageable config's KV
state in the physical page pool, and the pool may only change KV
*residency*, never tokens.

Fast in-process tier: the engine built on a float32 smoke config (the
residency follows ``paged.paged_supported``, through
``serve/step.make_cells``) serves the float32 reference's best token at
every position (``perfbench/reference/dense_transformer.py``), fully
recycles the page pool, and keeps its allocator invariants under
``debug=True`` (``kv.check()`` on every slot reset).  Configs the pool
cannot hold (a windowed or recurrent arch) are served from per-slot
caches, token-identical to the static engine; a cache the page size
does not tile is rejected at construction, not discovered mid-decode.

Subprocess tier (4 forced host devices, like ``test_serve_sharded``):
paged engines at tp=1/2/4 on the same seeded request set — token
streams bit-identical to the tp=1 engine, scheduling decisions
identical, pool recycled.  Float32 for the same reason as the sharded
differential: at f32 reduction-order noise (~1e-7) sits far below greedy
top-2 margins, so bit-identity is the honest invariant; at bf16 a
near-tied argmax could flip on a single ulp.
"""
import dataclasses
import os
import subprocess
import sys

import pytest

# f32 program vs f32 reference: summation order only; a flipped token
# moves the served logit by the spread of the top logits
LOGIT_TOL = 1e-3


def _f32_smoke():
    from repro.configs import all_archs, smoke
    return dataclasses.replace(smoke(all_archs()["olmo-1b"]),
                               dtype="float32")


def test_paged_engine_matches_dense_single_device():
    """Served tokens are the float32 reference's best at page-walk depths
    1 and 2, with the same scheduling at both; the pool is recycled."""
    import jax
    from perfbench.reference import dense_transformer as ref
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.loadgen import LoadSpec, make_requests
    cfg = _f32_smoke()
    key = jax.random.key(0)
    params = registry.init_params(cfg, key)
    spec = LoadSpec(n_requests=6, rate_rps=0.0, prompt_lens=(8, 16),
                    max_new_tokens=6, vocab_size=cfg.vocab_size, seed=3)

    streams, logs = {}, {}
    for depth in (1, 2):
        eng = ContinuousEngine(cfg, params, n_slots=4, cache_len=64,
                               block_size=8, page_buffer_depth=depth,
                               debug=True)
        assert eng.paged
        reqs = eng.generate(make_requests(spec))
        eng.scheduler.check()
        assert eng.kv.n_free == eng.kv.n_blocks
        assert all(len(r.generated) == 6 for r in reqs)
        # after a full sweep every device table row is back to all-trash
        assert (eng._tables_np == eng.kv.trash_page).all()
        out = ref.gaps(dataclasses.asdict(cfg), key,
                       [(r.prompt, r.generated) for r in reqs])
        assert max(float(g.max()) for g, _ in out) < LOGIT_TOL, depth
        streams[depth] = [list(r.generated) for r in reqs]
        logs[depth] = list(eng.scheduler.admit_log)
    assert streams[1] == streams[2] and logs[1] == logs[2]


def test_paged_rejects_untileable_cache():
    import jax
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    cfg = _f32_smoke()
    params = registry.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="divisible by block_size"):
        ContinuousEngine(cfg, params, n_slots=2, cache_len=60,
                         block_size=8)


@pytest.mark.parametrize("arch", ["h2o-danube-3-4b", "rwkv6-7b"])
def test_paged_rejects_unsupported_arch(arch):
    """A config the pool cannot hold (sliding window, recurrent state)
    is served from per-slot caches, token for token what the static
    engine serves on equal-length prompts."""
    import jax
    import numpy as np
    from repro.configs import all_archs, smoke
    from repro.launch.mesh import make_mesh
    from repro.models import registry
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.engine import Engine, Request
    from repro.serve.scheduler import ServeRequest
    cfg = smoke(all_archs()[arch])
    params = registry.init_params(cfg, jax.random.key(0))
    eng = ContinuousEngine(cfg, params, n_slots=2, cache_len=64,
                           block_size=8, debug=True)
    assert not eng.paged
    prompts = [np.arange(8, dtype=np.int32) % cfg.vocab_size,
               (np.arange(8, dtype=np.int32) + 3) % cfg.vocab_size]
    served = eng.generate([ServeRequest(prompt=p.copy(), max_new_tokens=6)
                           for p in prompts])
    static = Engine(cfg, make_mesh((1, 1), ("data", "model")),
                    batch_size=2, cache_len=64, params=params)
    want = static.generate([Request(prompt=p.copy(), max_new_tokens=6)
                            for p in prompts])
    assert [r.generated for r in served] == [r.generated for r in want]
    assert eng.kv.n_free == eng.kv.n_blocks


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

def run(tp, depth=2):
    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS,
                           cache_len=CACHE_LEN, block_size=BS,
                           tp_size=tp, page_buffer_depth=depth, debug=True)
    assert eng.paged
    spec = LoadSpec(n_requests=6, rate_rps=0.0, prompt_lens=(8, 16),
                    max_new_tokens=MAX_NEW, vocab_size=cfg.vocab_size,
                    seed=3)
    reqs = eng.generate(make_requests(spec))
    eng.scheduler.check()
    assert eng.kv.n_free == eng.kv.n_blocks, tp
    assert (eng._tables_np == eng.kv.trash_page).all(), tp
    return eng, [list(r.generated) for r in reqs]

# the single-device engine is the reference stream
engines = {}
engines[1], ref = run(1)
assert all(len(t) == MAX_NEW for t in ref)

# paged engines at every tensor-parallel width: bit-identical tokens —
# the pool (split over 'model' on the fused head axis at tp>1) changes
# placement, nothing else
for tp in (2, 4):
    eng, paged_toks = run(tp)
    engines[tp] = eng
    assert paged_toks == ref, (tp, paged_toks, ref)
    assert list(eng.scheduler.admit_log) == list(
        engines[1].scheduler.admit_log), tp

# the paged pool really is sharded at tp>1: per-layer pool leaves split
# over the fused-head axis, tables/token scalars replicated
pool = engines[2]._state
leaf = next(iter(pool.values()))
n_shards = {len(d.sharding.device_set) for d in pool.values()}
assert n_shards == {2}, n_shards
assert leaf.sharding.shard_shape(leaf.shape)[-2] == leaf.shape[-2] // 2

# buffer depth is a placement-free knob too
_, d4 = run(1, depth=4)
assert d4 == ref

# the Pallas kernel (interpreted here) runs once per 'model' shard inside
# shard_map, since the compiler cannot partition a Mosaic call: same stream
from repro import runtime
with runtime.use_policy(paged_attention_impl="pallas"):
    _, kern = run(2)
assert kern == ref, (kern, ref)

print("ALL_OK")
"""


def test_paged_engine_differential_4dev():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ALL_OK" in out.stdout, out.stdout + out.stderr
