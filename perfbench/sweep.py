"""Find the knee of an open traffic mix on the chip: the highest offered
rate the engine sustains.

    python3 perfbench/sweep.py --workload olmo1b.chat --seed 5 \\
        --seconds 20 --rates 2,3,4,5,6

One process builds the cell's engine once and serves the mix at each rate
in turn (the ``rate_rps`` of the traffic file replaced), one JSON line per
rate: requests, how many finished inside the drain, TTFT p50/p90, the
inter-token gap p99, queue wait p90, requests still queued when the
window closed, and output tokens/s. The mix's file then states 0.8 x the
knee as its ``rate_rps``.
"""
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

import numpy as np  # noqa: E402

from perfbench import spec, sut, traffic  # noqa: E402
from perfbench.metrics import itl_p99_ms  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python3 perfbench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(spec.ROOT,
                                                           ".jax_cache")
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep.py: needs a TPU")
    from repro import runtime
    runtime.enable_compile_cache()
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    c = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    _, eng = sut.build(c, sut.seed_key(args.seed))
    sut.warm(eng, mix, c["vocab_size"])
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        reqs = sut.make_requests(traffic.requests(m, c["vocab_size"],
                                                  args.seed, args.seconds))
        t0 = time.perf_counter()
        eng.run(reqs, deadline_s=args.seconds + m["drain_s"])
        wall = time.perf_counter() - t0
        ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
        wait = [r.queue_wait_s for r in reqs if r.queue_wait_s is not None]
        queued = [ev.queued for ev in eng.step_log
                  if ev.now <= args.seconds]
        print(json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "finished": sum(r.done for r in reqs),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "itl_p99_ms": float(np.percentile(
                itl_p99_ms.gaps(eng.step_log), 99)) * 1e3,
            "queue_wait_p90_ms": float(np.percentile(wait, 90)) * 1e3,
            "queued_at_close": queued[-1] if queued else 0,
            "out_tok_s": sum(len(r.generated) for r in reqs) / wall,
            "wall_s": wall}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
