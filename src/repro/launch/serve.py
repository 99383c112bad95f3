"""Serving driver: synthetic offered load through the serving engines.

Default path is the continuous-batching engine (slot admission, per-slot
KV accounting); every request's latency decomposition — queue wait,
TTFT, prefill, per-token decode — is printed per request, with a
throughput summary at the end.  ``--static`` routes the same workload
through the run-to-completion reference engine instead (no per-stage
stamps there; it reports tokens and wall time only).

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
        --requests 8 --rate 20 --max-new 16

``--arch`` builds the published config (olmo-1b: 16 layers, d_model
2048, ~1.2B bf16 params) with random params from ``--seed``; ``--smoke``
swaps in the reduced same-family config (``configs.smoke``) that the CPU
tests and examples use.  ``chip_smoke.py`` at the repo root drives the
same functions below (``parse_args`` -> ``load_model`` -> ``build_engine``
-> ``make_load`` -> ``serve``) at olmo-1b's published widths on a TPU.

``--rate 0`` (the default) submits everything as one burst; a positive
rate drives evenly spaced arrivals at that many requests per second —
the load-generator behind the ``serve.load_sweep`` experiment.

``--tp-size N`` makes the continuous engine tensor-parallel: decode and
prefill run through the mesh-aware cells in ``serve/step.py`` over N
devices (``--devices`` fabricates host devices for it, which is why jax
is imported only after argument parsing — the XLA flag must be set
before the backend initializes).

The continuous engine keeps the KV state of every config the page pool
can hold in the physical page pool (``serve/paged.py``): decode attends
through the ragged paged-attention kernel with ``--buffer-depth`` page
loads in flight.  Windowed, recurrent and hybrid archs keep per-slot
caches (``serve/step.make_cells`` decides).

``--trace FILE`` replays a recorded JSONL trace (arrivals, prompts,
generation budgets, priority classes — ``serve/loadgen.py``) instead of
generating synthetic load; ``--save-trace FILE`` records whatever stream
was served so a run can be re-offered verbatim.  ``--slo`` arms the
scheduler with the ``serve_slo_targets`` runtime policy: admission goes
priority-aware with preemption and shed, and the summary reports
per-class SLO attainment (DESIGN.md section 15).  ``--classes`` cycles
the given priority classes over generated requests when no trace
supplies them.

``--trace-out PATH`` attaches the unified span tracer (``repro.obs``,
DESIGN.md section 16) to the run and saves the Chrome-trace-event JSON —
engine-loop phases, scheduler decision instants, one track per decode
slot, pool/queue counters — loadable in Perfetto or chrome://tracing.
``--log-cap N`` ring-buffers the engine's step log and the scheduler's
admit/shed logs at N entries (evictions counted and reported).
"""
from __future__ import annotations

import argparse
import os
import time


def _fmt_ms(v) -> str:
    return f"{v * 1e3:.1f}ms" if v is not None else "-"


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and cross-check the CLI flags.  ``--devices`` sets the XLA
    host-device flag first, so it only takes effect in a process that has
    not initialized jax yet (the ``python -m`` entry point)."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve",
        description="Serve a synthetic request stream and report "
                    "per-request latency decomposition.")
    ap.add_argument("--arch", default="olmo-1b",
                    help="architecture at its published widths (see "
                         "configs/); --smoke reduces it")
    ap.add_argument("--smoke", action="store_true",
                    help="use the tiny same-family smoke config (the CPU "
                         "tests' size) instead of the published widths")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (continuous) / batch size (static)")
    ap.add_argument("--cache-len", type=int, default=128,
                    help="per-slot KV cache positions")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV allocator block granularity, in tokens")
    ap.add_argument("--max-new", type=int, default=16,
                    help="new tokens generated per request")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of synthetic requests")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in requests/s (0 = one burst)")
    ap.add_argument("--prompt-lens", default="8,16",
                    help="comma-separated prompt lengths, cycled")
    ap.add_argument("--arrivals", choices=("uniform", "poisson"),
                    default="uniform",
                    help="arrival process at --rate: evenly spaced or "
                         "seeded poisson")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random params and the load "
                         "generator (prompts + poisson arrivals)")
    ap.add_argument("--static", action="store_true",
                    help="use the static run-to-completion engine "
                         "(burst submission only)")
    ap.add_argument("--fabric", default="clean",
                    help="degraded-fabric condition injected into the "
                         "engine's admission/decode path: one of the "
                         "canonical scenarios (clean, jitter, straggler, "
                         "lossy, throttle; repro.fabric)")
    ap.add_argument("--tp-size", type=int, default=1,
                    help="tensor-parallel decode over this many devices "
                         "(continuous engine; params + per-slot KV "
                         "sequence sharded over a 'model' axis)")
    ap.add_argument("--buffer-depth", type=int, default=2,
                    help="paged-attention page buffers in flight (DMA "
                         "double-buffering on TPU, page-gather width in "
                         "the XLA twin)")
    ap.add_argument("--devices", type=int, default=0,
                    help="fabricate N host devices (XLA flag; must be set "
                         "before jax initializes, hence a CLI flag)")
    ap.add_argument("--trace", default="",
                    help="replay a recorded JSONL trace file (arrivals, "
                         "prompts, budgets, priority classes) instead of "
                         "generating synthetic load (continuous engine "
                         "only)")
    ap.add_argument("--save-trace", default="",
                    help="record the served request stream to this JSONL "
                         "file, replayable via --trace")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-driven admission: priority classes, "
                         "preemption and shed per the serve_slo_targets "
                         "runtime policy (continuous engine only)")
    ap.add_argument("--classes", default="",
                    help="comma-separated priority classes cycled over "
                         "generated requests (e.g. interactive,batch); "
                         "ignored when --trace supplies classes")
    ap.add_argument("--trace-out", default="",
                    help="save the run's unified span trace (engine loop, "
                         "scheduler decisions, per-slot request spans, "
                         "pool counters — repro.obs) as Chrome-trace-event "
                         "JSON at this path; open in Perfetto or "
                         "chrome://tracing (continuous engine only)")
    ap.add_argument("--log-cap", type=int, default=0,
                    help="ring-buffer cap on the engine's step log and the "
                         "scheduler's admit/shed logs (0 = unbounded); "
                         "evictions are counted and reported, not silent")
    args = ap.parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    import jax
    from repro.fabric import canonical_conditions
    canon = canonical_conditions()
    if args.fabric not in canon:
        ap.error(f"--fabric {args.fabric!r}: unknown condition "
                 f"(canonical: {', '.join(sorted(canon))})")
    if args.static and args.fabric != "clean":
        ap.error("--fabric injects into the continuous engine's "
                 "admission/decode path; the static engine has no such "
                 "hooks (drop --static)")
    if args.static and args.rate:
        # the static engine has no arrival model — chunks run back to
        # back; reporting a tok/s against a never-offered rate would make
        # the two engines' numbers incomparable
        ap.error("--static serves one burst; it cannot pace arrivals "
                 "(drop --rate or use the continuous engine)")
    if args.tp_size < 1:
        ap.error("--tp-size must be >= 1")
    if args.static and args.tp_size > 1:
        ap.error("--tp-size shards the continuous engine's decode cells; "
                 "the static engine has no sharded path (drop --static)")
    if args.tp_size > len(jax.devices()):
        ap.error(f"--tp-size {args.tp_size} exceeds the "
                 f"{len(jax.devices())} visible device(s) "
                 f"(fabricate more with --devices N)")
    if args.buffer_depth < 1:
        ap.error("--buffer-depth must be >= 1")
    if args.static and (args.trace or args.slo):
        ap.error("--trace/--slo drive the continuous engine's arrival "
                 "pacing and admission policy; the static engine has "
                 "neither (drop --static)")
    if args.trace and args.classes:
        ap.error("--classes assigns priorities to generated requests; "
                 "a --trace already carries its own (drop one)")
    if args.static and args.save_trace:
        ap.error("--save-trace records the continuous engine's request "
                 "stream (drop --static)")
    if args.static and (args.trace_out or args.log_cap):
        ap.error("--trace-out/--log-cap instrument the continuous "
                 "engine's loop; the static engine has no span "
                 "instrumentation (drop --static)")
    if args.log_cap < 0:
        ap.error("--log-cap must be >= 0 (0 = unbounded)")
    return args


def load_model(args):
    """(config, params): the published config, or its smoke reduction
    with ``--smoke``; random params drawn from ``--seed``."""
    import jax
    from repro.configs import all_archs, smoke
    from repro.models import registry
    cfg = all_archs()[args.arch]
    if args.smoke:
        cfg = smoke(cfg)
    return cfg, registry.init_params(cfg, jax.random.key(args.seed))


def build_engine(args, cfg, params):
    """The continuous engine the flags describe (slots, page walk depth,
    tensor parallelism, fabric, SLO policy, tracer, log cap)."""
    from repro.fabric import ServeFabric, canonical_conditions
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.scheduler import SLOPolicy
    fabric = None
    if args.fabric != "clean":
        fabric = ServeFabric(canonical_conditions()[args.fabric])
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer(metadata={"cli": "repro.launch.serve",
                                  "arch": cfg.name, "fabric": args.fabric})
    return ContinuousEngine(cfg, params, n_slots=args.batch,
                            cache_len=args.cache_len,
                            block_size=args.block_size, fabric=fabric,
                            tp_size=args.tp_size,
                            page_buffer_depth=args.buffer_depth,
                            slo=SLOPolicy.from_runtime() if args.slo
                            else None,
                            tracer=tracer, log_cap=args.log_cap or None)


def make_load(args, cfg):
    """The request stream: a replayed ``--trace`` or synthetic load from
    the generator flags (``--classes`` cycled over it)."""
    from repro.serve.loadgen import LoadSpec, load_trace, make_requests
    if args.trace:
        return load_trace(args.trace).requests
    reqs = make_requests(LoadSpec(
        n_requests=args.requests, rate_rps=args.rate,
        prompt_lens=tuple(int(x) for x in args.prompt_lens.split(",")),
        max_new_tokens=args.max_new, vocab_size=cfg.vocab_size,
        seed=args.seed, arrivals=args.arrivals))
    if args.classes:
        names = [c.strip() for c in args.classes.split(",") if c.strip()]
        for i, r in enumerate(reqs):
            r.priority = names[i % len(names)]
    return reqs


def serve(args, eng, reqs) -> float:
    """Run ``reqs`` through ``eng`` and print the per-request latency
    decomposition and the run summary.  Returns the wall seconds of
    ``eng.run``."""
    if args.save_trace:
        from repro.serve.loadgen import save_trace
        save_trace(reqs, args.save_trace)
        print(f"[serve] trace saved to {args.save_trace} "
              f"({len(reqs)} requests)")
    t0 = time.perf_counter()
    eng.run(reqs)
    elapsed = time.perf_counter() - t0
    fabric = eng.fabric
    if fabric is not None:
        print(f"[serve] fabric '{args.fabric}': "
              f"{fabric.condition.describe()} — injected "
              f"{fabric.stalled_s['admit'] * 1e3:.0f}ms into admission, "
              f"{fabric.stalled_s['decode'] * 1e3:.0f}ms into decode "
              "ticks")
    for i, r in enumerate(reqs):
        tag = f" [{r.priority}]" if (args.slo or args.trace
                                     or args.classes) else ""
        shed = f" SHED({r.shed_reason})" if r.t_shed is not None else ""
        print(f"[serve] req {i}{tag}: prompt={len(r.prompt)} "
              f"tokens={len(r.generated)} "
              f"queue={_fmt_ms(r.queue_wait_s)} "
              f"ttft={_fmt_ms(r.ttft_s)} "
              f"prefill={_fmt_ms(r.prefill_s)} "
              f"tpot={_fmt_ms(r.tpot_s)}{shed}")
    policy = eng.scheduler.slo
    if policy is not None:
        sched = eng.scheduler
        for cname in sorted({r.priority for r in reqs}):
            cls = policy.slo_for(cname)
            creqs = [r for r in reqs if r.priority == cname]
            hits = [r for r in creqs if r.done
                    and r.ttft_s is not None and r.ttft_s <= cls.ttft_s
                    and (r.tpot_s is None or r.tpot_s <= cls.tpot_s)]
            print(f"[serve] class {cname}: "
                  f"{len(hits)}/{len(creqs)} in SLO "
                  f"(ttft<={cls.ttft_s * 1e3:.0f}ms, "
                  f"tpot<={cls.tpot_s * 1e3:.0f}ms), "
                  f"{sum(r.t_shed is not None for r in creqs)} shed, "
                  f"{sum(r.n_preempted for r in creqs)} preempt "
                  f"cycle(s)")
        print(f"[serve] slo: {len(sched.admit_log)} admissions, "
              f"{len(sched.preempt_log)} preemptions, "
              f"{len(sched.shed_log)} shed")
    if args.log_cap:
        dropped = (eng.step_log.dropped
                   + eng.scheduler.admit_log.dropped
                   + eng.scheduler.shed_log.dropped)
        print(f"[serve] log cap {args.log_cap}: "
              f"{len(eng.step_log)} step events kept, "
              f"{dropped} evicted (step={eng.step_log.dropped}, "
              f"admit={eng.scheduler.admit_log.dropped}, "
              f"shed={eng.scheduler.shed_log.dropped})")
    if args.trace_out:
        eng.tracer.save(args.trace_out)
        print(f"[serve] trace: {args.trace_out} "
              f"({len(eng.tracer.events)} events; load in Perfetto or "
              f"chrome://tracing)")
    _summary(args, reqs, elapsed, paged=eng.paged)
    return elapsed


def _summary(args, reqs, elapsed: float, paged: bool = False) -> None:
    toks = sum(len(r.generated) for r in reqs)
    mode = "static" if args.static else (
        f"continuous tp={args.tp_size}" if args.tp_size > 1 else
        "continuous")
    if paged:
        mode += f" paged(depth={args.buffer_depth})"
    if args.slo:
        mode += " slo"
    offered = "trace" if args.trace else f"{args.rate or 'burst'} req/s"
    print(f"[serve] {mode}: {len(reqs)} requests, {toks} tokens in "
          f"{elapsed:.2f}s -> {toks / elapsed:.1f} tok/s "
          f"(offered {offered})")


def main(argv=None):
    args = parse_args(argv)
    from repro import runtime
    runtime.enable_compile_cache()
    cfg, params = load_model(args)
    if not args.static:
        eng = build_engine(args, cfg, params)
        serve(args, eng, make_load(args, cfg))
        return
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import Engine, Request
    eng = Engine(cfg, make_host_mesh(1, 1), batch_size=args.batch,
                 cache_len=args.cache_len, params=params)
    reqs = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
            for r in make_load(args, cfg)]
    t0 = time.perf_counter()
    for i in range(0, len(reqs), args.batch):
        eng.generate(reqs[i:i + args.batch])
    elapsed = time.perf_counter() - t0
    for i, r in enumerate(reqs):
        print(f"[serve] req {i}: prompt={len(r.prompt)} "
              f"tokens={len(r.generated)} (static batch — no "
              f"per-stage stamps)")
    _summary(args, reqs, elapsed)


if __name__ == "__main__":
    main()
