import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           "--xla_disable_hlo_passes=all-reduce-promotion")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count on first init) — this file fabricates the 512 placeholder host
devices the production meshes need.

For each live cell (see configs.base.live_shapes for the long_500k skip
rule) this lowers and compiles the real step function — train_step for
train_4k, prefill_step for prefill_32k, decode_step for decode cells —
against ShapeDtypeStruct inputs (no allocation), prints
``memory_analysis()`` / ``cost_analysis()``, parses collective wire bytes
from the HLO, and emits the three-term roofline (analysis/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import argparse
import json
import time
import traceback

import jax

from repro.analysis import roofline as rf
from repro.analysis.peaks import DRYRUN_KIND
from repro.configs import all_archs, live_shapes
from repro.configs.base import SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.parallel import sharding
from repro.serve import step as sstep
from repro.train import step as tstep
from repro.train.optimizer import OptConfig


def lower_cell(cfg, shape, mesh, options=None, sp=False, dp=None,
               remat=None):
    """Returns (lowered, ctx).  Chooses the right step function per shape."""
    import dataclasses
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if shape.kind == "train":
        options = options or tstep.TrainOptions(
            dp_method=dp or ("int8_a2a" if "pod" in mesh.axis_names
                             else "stock"),
            sequence_parallel=sp,
            opt=OptConfig(state_dtype=cfg.opt_state_dtype))
        jitted, ctx, state_shape = tstep.jit_train_step(cfg, shape, mesh,
                                                        options)
        bspec = registry.input_specs(cfg, shape)
        lowered = jitted.lower(state_shape, bspec)
        return lowered, ctx
    if shape.kind == "prefill":
        jitted, ctx, params_shape = sstep.jit_prefill_step(cfg, shape, mesh)
        lowered = jitted.lower(params_shape, registry.input_specs(cfg, shape))
        return lowered, ctx
    jitted, ctx, params_shape, cache_shape = sstep.jit_decode_step(
        cfg, shape, mesh)
    lowered = jitted.lower(params_shape, cache_shape,
                           registry.input_specs(cfg, shape))
    return lowered, ctx


def run_cell(cfg, shape, mesh_name: str, verbose: bool = True,
             sp: bool = False, dp=None, remat=None):
    multi_pod = mesh_name == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    lowered, _ = lower_cell(cfg, shape, mesh, sp=sp, dp=dp, remat=remat)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    ma = compiled.memory_analysis()
    if verbose:
        print(f"  memory_analysis: args={ma.argument_size_in_bytes/1e9:.3f}GB "
              f"out={ma.output_size_in_bytes/1e9:.3f}GB "
              f"temp={ma.temp_size_in_bytes/1e9:.3f}GB "
              f"peak={ma.peak_memory_in_bytes/1e9:.3f}GB per device")
        ca = compiled.cost_analysis()
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e} per device")
    # the placeholder devices stand in for v5e pods: the roofline takes
    # that chip's peaks (analysis/peaks.py)
    cell = rf.analyze(cfg, shape, mesh_name, n_chips, compiled,
                      device_kind=DRYRUN_KIND)
    out = cell.to_dict()
    out["lower_s"] = t1 - t0
    out["compile_s"] = t2 - t1
    out["output_bytes"] = float(ma.output_size_in_bytes)
    out["temp_bytes"] = float(ma.temp_size_in_bytes)
    if verbose:
        print(f"  roofline: compute={cell.compute_s*1e3:.2f}ms "
              f"memory={cell.memory_s*1e3:.2f}ms "
              f"collective={cell.collective_s*1e3:.2f}ms "
              f"-> {cell.bottleneck}-bound "
              f"(roofline fraction {cell.roofline_fraction:.1%}, "
              f"useful {cell.useful_ratio:.1%})")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel TP (perf variant)")
    ap.add_argument("--dp", default=None,
                    help="override DP method (stock | int8_a2a | int8_ring)")
    ap.add_argument("--remat", default=None,
                    help="override remat policy (none|full|dots_saveable)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = all_archs()
    names = [args.arch] if args.arch else list(archs)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    failures = []
    for name in names:
        cfg = archs[name]
        shapes = ([SHAPES[args.shape]] if args.shape
                  else live_shapes(cfg))
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{name}__{shape.name}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                print(f"[cell] {tag}")
                try:
                    out = run_cell(cfg, shape, mesh_name, sp=args.sp,
                                   dp=args.dp, remat=args.remat)
                    with open(path, "w") as f:
                        json.dump(out, f, indent=1)
                except Exception as e:
                    failures.append(tag)
                    print(f"  FAILED: {type(e).__name__}: {e}")
                    traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("all cells passed")


if __name__ == "__main__":
    main()
