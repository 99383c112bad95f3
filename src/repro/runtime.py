"""Runtime policy: which implementation backs each hot-spot op.

The dry-run / production-XLA path uses pure-jnp ("xla") implementations; on
real TPUs the Pallas kernels are enabled; CPU tests run Pallas in interpret
mode.  The offload planner (core/planner.py) can also flip these switches.
"""
from __future__ import annotations

import os
import threading
from contextlib import contextmanager

# fixed, checkout-relative: the cache's entries key on it, so a directory
# that moved between runs would never hit
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_DEFAULT = {
    "attention_impl": "xla",    # xla | pallas
    "rwkv_impl": "xla",         # xla | pallas
    "quant_impl": "auto",       # auto | xla | pallas — auto routes payloads
    #                             above collectives.PALLAS_QUANT_MIN_SIZE
    #                             through the Pallas kernels
    "paged_attention_impl": "auto",  # auto | xla | pallas — the paged-KV
    #                             decode attention (kernels/paged_attention
    #                             via kernels/ops.paged_attention): auto
    #                             takes the Pallas DMA-pipelined kernel on
    #                             backends with a compiled lowering and the
    #                             pure-XLA twin elsewhere; pallas forces
    #                             the kernel (interpreted on CPU — the
    #                             correctness-test path)
    "pallas_interpret": None,   # None = auto: interpreted on CPU, compiled
    #                             on TPU/GPU (kernels.quant.resolve_interpret
    #                             keys on the backend); booleans force
    "overlap_schedule": "auto",  # auto | serial | pipelined — bucket-chain
    #                             issue order for compressed gradient
    #                             collectives (parallel/overlap.py); auto
    #                             pipelines when a tree packs into more
    #                             than one bucket.  The headroom_overlap
    #                             experiment pins each arm explicitly.
    "serve_prefill_per_step": 1,  # continuous-batching engine: max queued
    #                             requests admitted (prefilled) per engine
    #                             step, interleaved with the in-flight
    #                             decode batch (serve/continuous.py);
    #                             higher drains queues faster at the cost
    #                             of decode stalls (TPOT spikes)
    "serve_headroom_min_gflops": 1.0,  # planner rule 5: serving offload is
    #                             profitable only while the probe kernel
    #                             beside the engine clears this FLOP/s
    #                             floor at every sustained load level
    #                             (core/planner.serve_offload_assessment)
    "fabric_p99_inflation_max": 3.0,  # planner rule 5, degraded-fabric arm:
    #                             tolerated p99 TTFT/TPOT inflation (x vs
    #                             the clean-fabric run) before the serve
    #                             offload verdict is withdrawn
    #                             (core/planner.fabric_sensitivity_assessment
    #                             consuming fabric.serve_tail records)
    "serve_slo_targets": {      # per-class SLO targets (seconds) consumed by
        #                         scheduler.SLOPolicy.from_runtime — the
        #                         launch.serve --slo defaults; rank orders
        #                         admission (lower = higher priority),
        #                         shed_after_s is the queue-wait budget
        #                         (DESIGN.md section 15)
        "interactive": {"rank": 0, "ttft_s": 0.5, "tpot_s": 0.25},
        "standard": {"rank": 1, "ttft_s": 2.0, "tpot_s": 0.5},
        "batch": {"rank": 2, "ttft_s": 10.0, "tpot_s": 2.0,
                  "shed_after_s": 10.0},
    },
    "obs_trace": False,         # unified span tracing (repro.obs): True
    #                             makes every new ContinuousEngine build
    #                             its own Tracer (timestamps on the
    #                             engine clock) instead of the disabled
    #                             null tracer; the CLI --trace-out flags
    #                             install a thread-local tracer without
    #                             touching this knob (DESIGN.md sec. 16)
    "serve_slo_attainment_min": 0.9,  # planner rule 5, SLO arm: when
    #                             serve.slo_sweep records are present the
    #                             offload verdict additionally requires the
    #                             highest-priority class to attain its SLO
    #                             at this fraction at every sustained level
    #                             (core/planner.serve_offload_assessment)
}

_local = threading.local()


def policy() -> dict:
    if not hasattr(_local, "policy"):
        _local.policy = dict(_DEFAULT)
    return _local.policy


@contextmanager
def use_policy(**kwargs):
    prev = dict(policy())
    policy().update(kwargs)
    try:
        yield policy()
    finally:
        _local.policy = prev


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this before their first compile (never on import).
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``<checkout>/.jax_cache``.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
