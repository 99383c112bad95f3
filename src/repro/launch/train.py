"""End-to-end training driver.

Runs on whatever devices exist (CPU here, TPU pod in production): builds the
mesh, the sharded train step, the deterministic data pipeline, checkpoint
manager and the fault-tolerant loop.  The offload planner can pick the DP
method from the dry-run roofline of the corresponding cell (--plan).

Example (CPU, ~100M params, a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --scale 0.4 \
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax

from repro import runtime
from repro.checkpoint.manager import CheckpointManager
from repro.configs import all_archs, smoke
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.parallel import sharding
from repro.train import loop as tloop, step as tstep
from repro.train.optimizer import OptConfig


def scaled_config(cfg, scale: float):
    """Geometric down-scale of a config (keeps family/topology)."""
    if scale >= 1.0:
        return cfg
    d = max(128, int(cfg.d_model * scale) // 128 * 128)
    heads = max(4, int(cfg.num_heads * scale))
    kv = max(1, min(cfg.num_kv_heads, heads))
    return dataclasses.replace(
        cfg, name=cfg.name + f"-x{scale}", d_model=d,
        num_layers=max(2, int(cfg.num_layers * scale)),
        num_heads=heads, num_kv_heads=kv, head_dim=d // heads,
        d_ff=max(256, int(cfg.d_ff * scale) // 128 * 128),
        vocab_size=min(cfg.vocab_size, 32000),
        num_experts=min(cfg.num_experts, 8) if cfg.num_experts else 0,
        layer_group=1, attn_period=min(cfg.attn_period, 4) if cfg.attn_period else 0,
        rwkv_head_dim=64 if d % 64 == 0 else 32,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--scale", type=float, default=0.4)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dp-method", default="stock")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--plan", default=None,
                    help="dry-run JSON to derive the offload plan from")
    ap.add_argument("--smoke", action="store_true",
                    help="use the tiny smoke config instead of --scale")
    ap.add_argument("--trace-out", default="",
                    help="save a Chrome-trace-event JSON span timeline of "
                         "the run (per-step and checkpoint spans) at PATH")
    args = ap.parse_args()
    runtime.enable_compile_cache()

    base = all_archs()[args.arch]
    cfg = smoke(base) if args.smoke else scaled_config(base, args.scale)
    cfg = dataclasses.replace(cfg, remat="none")
    mesh = make_host_mesh(args.data_mesh, args.model_mesh)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)

    opts = tstep.TrainOptions(
        dp_method=args.dp_method, microbatches=args.microbatches,
        remat=False,
        opt=OptConfig(lr=args.lr, warmup_steps=20,
                      decay_steps=max(args.steps, 21)))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: registry.init_params(cfg, jax.random.key(0)))))
    if args.plan:
        from repro.core.headroom import RooflineTerms
        from repro.core.planner import make_plan
        from repro.core.stressors import run_suite
        d = json.load(open(args.plan))
        plan = make_plan(RooflineTerms(d["compute_s"], d["memory_s"],
                                       d["collective_s"]),
                         run_suite(duration=0.1),
                         multi_pod="pod" in mesh.axis_names,
                         # gradients cross the pod axis as fp32 bucket
                         # buffers — the planner's bucket-count (and so
                         # overlap) estimate keys on this
                         grad_bytes=4 * n_params)
        print("[plan]", *plan.notes, sep="\n  ")
        opts = dataclasses.replace(opts, dp_method=plan.dp_method
                                   if "pod" in mesh.axis_names else "stock",
                                   microbatches=plan.microbatches,
                                   dp_overlap=plan.dp_overlap)
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={len(jax.devices())} mesh={dict(mesh.shape)}")

    ctx = sharding.ShardingCtx(mesh, sharding.train_rules(False))
    state = tstep.make_train_state(cfg, opts, jax.random.key(0))
    state = jax.device_put(state, tstep.state_shardings(
        jax.eval_shape(lambda: state), ctx))
    stepf, _ = tstep.make_train_step(cfg, shape, mesh, opts)
    bspec = tstep.batch_shardings(registry.input_specs(cfg, shape), ctx)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch,
                      frames_dim=cfg.d_model if cfg.family == "encdec" else 0,
                      patches=cfg.num_patches, d_model=cfg.d_model)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start = 0
    if mgr.latest_step() is not None:
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        state, start = mgr.restore(
            abstract, shardings=tstep.state_shardings(abstract, ctx))
        print(f"[train] resumed from step {start}")
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer(metadata={"cli": "repro.launch.train",
                                  "arch": cfg.name})
    import contextlib
    with contextlib.ExitStack() as stack:
        if tracer is not None:
            from repro.obs import trace as obs_trace
            stack.enter_context(obs_trace.use(tracer))
        state, hist = tloop.train_loop(
            jax.jit(stepf, donate_argnums=0), state, dcfg, bspec, mgr,
            tloop.LoopConfig(total_steps=args.steps,
                             checkpoint_every=args.ckpt_every, log_every=10),
            start_step=start)
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"[train] trace: {args.trace_out} "
              f"({len(tracer.events)} events)")
    if hist:
        print(f"[train] done: loss {hist[0]['loss']:.4f} -> "
              f"{hist[-1]['loss']:.4f} over {len(hist)} steps")


if __name__ == "__main__":
    main()
