"""Read the numbers ``correct`` compares, and the control's, on the chip.

    python3 perfbench/control.py --workload nemo12b.decode \\
        --seeds 101,102,103 --seconds 15 --lower int8

For each seed, in one process: one run of the cell as the benchmark makes
it (set-up, window, freed engine), then the reference over the sampled
requests twice — as the benchmark reads it (the program's gap: a sound
run's reading) and with every dense matmul in ``--lower`` precision put
in the program's place (the control's gap, read at each served position
for the token the lower precision puts first), each judged by the
configuration's limits: ``correct`` for the program, ``control_correct``
for the control, which has to come out false. One JSON line per seed.
The benchmark's own runs never run the control.
"""
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from perfbench import run, spec  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python3 perfbench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--lower", default="int8", choices=("int8", "fp8"))
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(spec.ROOT,
                                                           ".jax_cache")
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("control.py: needs a TPU")
    from repro import runtime
    runtime.enable_compile_cache()
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    c = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(bench, args.workload, c, mix, seed, args.seconds,
                           False, t_start=time.perf_counter(),
                           lower=args.lower)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "lower": args.lower, "program": out["readings"],
                          "control": out["control"],
                          "limits": {k: v["limit"]
                                     for k, v in out["checks"].items()},
                          "correct": out["correct"],
                          "control_correct": out["control_correct"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
