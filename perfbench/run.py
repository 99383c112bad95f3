"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload olmo1b.chat --seed 1 --seconds 45 \
        --trace 0

From the root of a checkout, with no PYTHONPATH. ``--workload`` names a
cell of ``BENCHMARK.json``; its configuration, traffic mix and metric
readers are found by name (``spec.py``). A run:

1. exits nonzero, printing no result, unless JAX finds a TPU and as many
   chips as the cell asks for;
2. set-up: draws the parameters on the device from ``--seed`` in one
   jitted call, builds the paged serving engine, and serves one request
   per prompt bucket of the mix, so that every program the window calls
   is compiled (or loaded from the compilation cache at
   ``<checkout>/.jax_cache``) before it opens;
3. the window: offers the mix's requests (``traffic.py``) for
   ``--seconds`` seconds; an open mix is then served to completion, at
   most ``drain_s`` longer; a backlog is cut when the window closes;
4. reads the peak device memory, frees the engine, and checks a sample of
   the served tokens against the plain reference (``check.py``);
5. ``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
   profiles a few seconds inside the window and prints its per-layer
   metrics, the device's busy and traced seconds, and a breakdown.

Diagnostics (set-up compiles, compiles inside the window, peak memory,
generator lateness) go to stderr, ending with each compared number beside
its limit. The last line of stdout is the result, one JSON object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

# the fraction of the window before the profiler starts, and the most
# seconds it records (--trace 1): long enough that an open mix's slice
# holds arrivals, and so prefills, as well as decode steps
TRACE_AT, TRACE_LEN = 0.4, 6.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(prog="python3 perfbench/run.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True,
                    help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights, prompts and sample")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile inside the window, print per-layer "
                         "metrics")
    ap.add_argument("--trace-dir", default="",
                    help="also write the profile here, as trace.xplane.pb "
                         "(default: kept in memory only)")
    return ap.parse_args(argv)


def device_info():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def run_cell(bench, cell_name, c, mix, seed, seconds, traced, *,
             trace_dir=None, tamper=None, t_start=None, root=ROOT,
             lower=None) -> dict:
    """One run of a cell; returns the result object. ``tamper(engine)``
    may break the engine underneath (the fault tests); ``root`` is the
    checkout whose metric readers are used; ``lower`` also reads the
    lower-precision control's gap (``control.py``)."""
    from perfbench import check, peaks, spec, sut, traffic, xplane
    t_start = T_START if t_start is None else t_start
    clock = sut.CompileClock()
    key = sut.seed_key(seed)
    cfg, eng = sut.build(c, key)
    if tamper is not None:
        tamper(eng)
    profile = ((TRACE_AT * seconds, min(TRACE_LEN, 0.25 * seconds))
               if traced else None)
    rec = sut.Recorder(eng, profile)
    sut.warm(eng, mix, cfg.vocab_size)
    setup_compile_s, setup_compiles = clock.take()
    reqs = sut.make_requests(traffic.requests(mix, cfg.vocab_size, seed,
                                              seconds))
    open_mix = mix["kind"] == "open"
    deadline = seconds + mix["drain_s"] if open_mix else seconds
    setup_s = time.perf_counter() - t_start
    rec.window()
    t0 = time.perf_counter()
    eng.run(reqs, deadline_s=deadline)
    elapsed = time.perf_counter() - t0
    rec.close()
    _, window_compiles = clock.take()
    peak = sut.peak_bytes()
    step_log = list(eng.step_log)
    xspace = rec.xspace
    lateness = np.asarray(rec.lateness)
    del eng, rec
    gc.collect()

    log(f"set-up {setup_s:.3f}s, {setup_compiles} compiles/cache loads "
        f"taking {setup_compile_s:.3f}s")
    log(f"compiles inside the window: {window_compiles}")
    log(f"peak_bytes_in_use {peak}")
    if lateness.size:
        log(f"generator lateness p99 {np.percentile(lateness, 99) * 1e3:.3f}"
            f"ms over {lateness.size} submissions")
    done = sum(r.done for r in reqs)
    if open_mix:
        attempted, failed = len(reqs), len(reqs) - done
    else:
        attempted = sum(r.t_admit is not None or r.done for r in reqs)
        failed = 0
    log(f"window {elapsed:.3f}s: {attempted} requests attempted, {done} "
        f"finished, {failed} failed, "
        f"{sum(len(r.generated) for r in reqs)} tokens")
    ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
    if open_mix and ttft:
        q = np.percentile(ttft, [50, 75, 90, 100]) * 1e3
        log(f"ttft p50 {q[0]:.3f} p75 {q[1]:.3f} p90 {q[2]:.3f} max "
            f"{q[3]:.3f} ms over {len(ttft)} requests")

    t_check = time.perf_counter()
    v = check.verdict(c, key, reqs, seed, lower=lower)
    log(f"reference check over {v['sampled']} requests, "
        f"{v['sampled_tokens']} served tokens, "
        f"{time.perf_counter() - t_check:.3f}s; readings {v['readings']}")

    dev = device_info()
    dev["memory_peak_bytes"] = peak
    tr = None
    if xspace is not None:
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)
            with open(os.path.join(trace_dir, "trace.xplane.pb"), "wb") as fh:
                fh.write(xspace)
        tr = xplane.loads(xspace)
        dev["busy_s"] = tr.busy_s()
        dev["window_s"] = tr.window_s
    run = types.SimpleNamespace(
        cell=cell_name, config=c, traffic=mix, seed=seed, seconds=seconds,
        requests=reqs, step_log=step_log, elapsed_s=elapsed,
        setup_s=setup_s, setup_compile_s=setup_compile_s,
        window_compiles=window_compiles, trace=tr,
        peaks=peaks.peaks(dev["kind"]) if dev["platform"] == "tpu"
        else None)
    metrics = {}
    for m in spec.metrics(bench, cell_name, traced):
        value = spec.reader(m["name"], root).read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
        else:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": v["correct"], "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": dev}
    if tr is not None:
        out["breakdown"] = xplane.breakdown(tr)
    if lower:
        out["readings"] = v["readings"]
        out["control"] = v["control"]
        out["control_correct"] = v["control_correct"]
    out["checks"] = v["checks"]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    # the compilation cache the program keeps (runtime.enable_compile_cache
    # takes this variable where it is set): a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from perfbench import spec
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    c = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    import jax
    dev = device_info()
    if dev["platform"] != "tpu":
        log(f"needs a TPU; JAX found platform {dev['platform']!r} "
            f"({dev['count']} device(s))")
        return 2
    if dev["count"] < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} chips; JAX found "
            f"{dev['count']}")
        return 2
    from repro import runtime
    runtime.enable_compile_cache()
    # every program, however quick to compile, is cached: set-up then
    # does the same work in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"{args.workload}: {dev['count']} x {dev['kind']}, jax "
        f"{jax.__version__}, seed {args.seed}, {args.seconds}s, trace "
        f"{args.trace}")
    out = run_cell(bench, args.workload, c, mix, args.seed, args.seconds,
                   bool(args.trace), trace_dir=args.trace_dir or None)
    for name, chk in out["checks"].items():
        log(f"check {name} {chk['value']} limit {chk['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
