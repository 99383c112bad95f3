"""Serve olmo-1b at its published widths on a TPU and check what comes out.

    python chip_smoke.py              # one chip: the paged continuous engine
    python chip_smoke.py --chips 4    # tp=4 decode vs tp=1, int8 vs stock reduce

Runs from the repo root with no PYTHONPATH (``src`` goes on ``sys.path``
here), in one process that starts no child.  It drives the serving CLI's
own functions (``repro.launch.serve``: ``parse_args`` -> ``load_model`` ->
``build_engine`` -> ``make_load`` -> ``serve``) at olmo-1b's published
widths with random params from ``--seed``: 16 requests, prompts cycled over
128 and 512 tokens, 64 new tokens each, through
``ContinuousEngine(paged=True, n_slots=8, cache_len=1024, block_size=16)``.
Every check that fails ends the run with a nonzero exit.  When JAX finds
no TPU it exits nonzero before any work and prints no result.  The last
line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Bring-up readings only (compile and serve seconds, peak HBM): this is not
a benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402

# The published-width run: two prompt lengths only, because prefill
# compiles once per distinct prompt length.
SERVE_FLAGS = ["--arch", "olmo-1b", "--paged", "--batch", "8",
               "--cache-len", "1024", "--block-size", "16",
               "--requests", "16", "--prompt-lens", "128,512",
               "--max-new", "64"]

# What a compiled Pallas kernel is called in the program text: the proof
# that decode ran the kernel and not its XLA twin.
KERNEL_MARKER = "tpu_custom_call"

# Kernel vs f32 reference: the largest error over the largest |reference|.
# The kernel reads bf16 pages and a bf16 query and writes bf16; if the MXU
# takes bf16 operands, the query, the probabilities and the output each
# carry one bf16 rounding (2**-9 relative), and a score error of d moves
# the probabilities by about d.  For this model's scores (|s| of a few
# units) that stays near 2**-7 of the output scale; 2**-5 leaves room for
# it.  A wrong page, mask or head mapping moves the output by its own
# scale, 32x over the bound.
KERNEL_TOL = 2.0 ** -5

# tp=4 vs tp=1, first decode step: the largest logit difference over the
# largest |logit|.  The tensor-parallel program adds four bf16 partial sums
# where the single-device one rounds one f32-accumulated dot, in the two
# row-parallel matmuls of each of the 16 layers; those bf16 roundings
# (2**-9 each) accumulate to a few percent of a logit's spread.  A wrong
# shard mapping moves logits by their own scale.
TP_LOGIT_TOL = 2.0 ** -3

# int8 vs stock gradient reduce: relative L2 error of the int8 result.
# Rowwise int8 rounds each element to within amax/254 of its row: for a
# Gaussian row (amax ~ 4 sigma) that is ~1% rms per quantization, and the
# all-to-all form quantizes twice (before the exchange, and the reduced
# chunk before the gather).  Error feedback is off (one step), so ~1.5%
# is expected; 5% bounds it, and a dropped or misrouted chunk is ~100%.
REDUCE_TOL = 0.05

# Layers of the gradient tree the reduce check carries, each leaf at the
# published widths: 2 of olmo-1b's 16 (134M params per device).  The
# compile time of the bucketed chain grows with the payload (76 s for 2
# layers in a v5e ahead-of-time compile on a host CPU), and 2 layers
# already hold several 16 MiB buckets.
REDUCE_LAYERS = 2


class Probe:
    """Observes a paged engine's prefill and decode cells without changing
    what they return.

    It counts non-finite logits on the device (read once, at the end),
    keeps the first decode step's logits with the slots active then, and
    snapshots the first layer of the pool, the block tables and the lengths of
    the first step that has every slot active — the kernel check's
    input.  Its small device ops compile during the warm-up run."""

    def __init__(self, eng):
        self.eng = eng
        self.bad = jnp.zeros((), jnp.int32)
        self.first = None          # (logits, active slots)
        self.snapshot = None       # (pool layer, tables, lengths)
        self._warm = False
        count = jax.jit(lambda acc, x: acc + jnp.sum(~jnp.isfinite(x),
                                                     dtype=jnp.int32))
        snap = jax.jit(lambda pool_l, idx: (pool_l[0], idx + 1))
        prefill, decode = eng._prefill, eng._decode

        def probed_prefill(params, tokens):
            logits, caches = prefill(params, tokens)
            self.bad = count(self.bad, logits)
            return logits, caches

        def probed_decode(params, tok, idx, pool, tables):
            logits, pool = decode(params, tok, idx, pool, tables)
            self.bad = count(self.bad, logits)
            active = [s for s, _ in eng.scheduler.active()]
            if self.first is None:
                self.first = (logits, active)
            if not self._warm or (self.snapshot is None
                                  and len(active) == eng.n_slots):
                pool_l, lengths = snap(pool["l0"], idx)
                if self._warm:
                    self.snapshot = (pool_l, tables, lengths)
                self._warm = True
            return logits, pool

        eng._prefill, eng._decode = probed_prefill, probed_decode

    def reset(self):
        self.first = self.snapshot = None


class CompileClock:
    """Sums JAX's backend-compile events (compiles and persistent-cache
    loads) while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def take(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def fail(msg: str):
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str):
    print(f"[smoke] {'ok ' if ok else 'BAD'} {msg}")
    if not ok:
        fail(msg)


def serve_run(flags, clock: CompileClock):
    """Build the engine the flags describe, warm every shape it will use
    (two requests: one per prompt length), then serve the full load.
    Returns (cfg, engine, probe, requests, parsed flags)."""
    args = serve_cli.parse_args(flags)
    cfg, params = serve_cli.load_model(args)
    print(f"[smoke] config {cfg.name}: layers {cfg.num_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads} (kv {cfg.num_kv_heads}, "
          f"head dim {cfg.hd}), d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}")
    eng = serve_cli.build_engine(args, cfg, params)
    probe = Probe(eng)
    clock.take()               # params init + engine build
    warm = argparse.Namespace(**{**vars(args), "requests": 2, "max_new": 2})
    t0 = time.perf_counter()
    serve_cli.serve(warm, eng, serve_cli.make_load(warm, cfg))
    warm_s = time.perf_counter() - t0
    compile_s, n_compiles = clock.take()
    print(f"[smoke] tp={eng.tp_size} compile: {compile_s:.3f}s over "
          f"{n_compiles} programs (warm-up run {warm_s:.3f}s)")
    probe.reset()
    reqs = serve_cli.make_load(args, cfg)
    serve_s = serve_cli.serve(args, eng, reqs)
    _, n_window = clock.take()
    toks = sum(len(r.generated) for r in reqs)
    print(f"[smoke] tp={eng.tp_size} serve: {serve_s:.3f}s, "
          f"{sum(r.done for r in reqs)}/{len(reqs)} requests completed, "
          f"{toks} tokens generated, {n_window} compiles inside the window")
    return cfg, eng, probe, reqs, args


def check_requests(cfg, probe, reqs, max_new: int):
    check(all(r.done and len(r.generated) == max_new for r in reqs),
          f"every request finished with {max_new} tokens")
    toks = np.concatenate([np.asarray(r.generated) for r in reqs])
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          f"all {toks.size} tokens inside the vocab ({cfg.vocab_size})")
    bad = int(probe.bad)
    check(bad == 0, f"{bad} non-finite logits over every prefill and decode")


def check_kernel(cfg, probe, seed: int):
    """paged_attention_fwd over the engine's own pool layer, tables and
    lengths against paged_attention_ref in f32."""
    from repro.kernels.paged_attention import paged_attention_fwd
    from repro.kernels.ref import paged_attention_ref
    check(probe.snapshot is not None, "a decode step had every slot active")
    pool_l, tables, lengths = probe.snapshot
    q = jax.random.normal(jax.random.key(seed), (tables.shape[0],
                                                 cfg.num_heads, cfg.hd),
                          jnp.float32).astype(pool_l.dtype)
    got = jax.jit(paged_attention_fwd)(q, pool_l, tables,
                                        lengths).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_attention_ref)(
            q.astype(jnp.float32), pool_l.astype(jnp.float32), tables,
            lengths)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    lens = np.asarray(lengths)
    print(f"[smoke] kernel check over pool {tuple(pool_l.shape)} "
          f"{pool_l.dtype}, lengths {lens.min()}..{lens.max()}: "
          f"max|kernel-ref| {err:.6g}, max|ref| {scale:.6g}")
    check(err <= KERNEL_TOL * scale,
          f"paged_attention_fwd vs f32 paged_attention_ref: "
          f"{err / scale:.6g} of the output scale <= {KERNEL_TOL}")


def one_chip(seed: int, flags=SERVE_FLAGS) -> None:
    clock = CompileClock()
    cfg, eng, probe, reqs, args = serve_run(
        flags + ["--seed", str(seed)], clock)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    check_requests(cfg, probe, reqs, args.max_new)
    check(KERNEL_MARKER in eng.cells.decode_hlo_text(eng.params),
          f"decode program holds {KERNEL_MARKER} (the Pallas kernel)")
    check_kernel(cfg, probe, seed)


def four_chips(seed: int, flags=SERVE_FLAGS) -> None:
    """tp=4 paged serve against the tp=1 engine on device 0, then the
    bucketed int8 gradient reduce against stock pmean."""
    clock = CompileClock()
    flags = flags + ["--seed", str(seed)]
    runs = {}
    for tp in (1, 4):
        cfg, eng, probe, reqs, args = serve_run(
            flags + ["--tp-size", str(tp)], clock)
        check_requests(cfg, probe, reqs, args.max_new)
        logits, active = probe.first
        runs[tp] = ([list(r.generated) for r in reqs],
                    np.asarray(logits, np.float32)[active])
        if tp > 1:
            check(KERNEL_MARKER in eng.cells.decode_hlo_text(eng.params),
                  f"tp=4 decode program holds {KERNEL_MARKER}")
        del eng, probe
    (toks1, lg1), (toks4, lg4) = runs[1], runs[4]
    same = sum(a == b for t1, t4 in zip(toks1, toks4)
               for a, b in zip(t1, t4))
    total = sum(len(t) for t in toks1)
    print(f"[smoke] tp=4 vs tp=1 tokens: {same}/{total} agree, "
          f"{sum(a == b for a, b in zip(toks1, toks4))}/{len(toks1)} "
          f"requests identical")
    diff = float(np.max(np.abs(lg4 - lg1)))
    scale = float(np.max(np.abs(lg1)))
    print(f"[smoke] tp=4 vs tp=1 first decode step: max|dlogit| {diff:.6g}, "
          f"max|logit| {scale:.6g}")
    check(diff <= TP_LOGIT_TOL * scale,
          f"tp=4 logits within {TP_LOGIT_TOL} of the logit scale "
          f"({diff / scale:.6g})")
    reduce_check(cfg, seed, clock)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use (device 0) "
          f"{stats.get('peak_bytes_in_use')}")


def gradient_shapes(cfg, n_layers: int):
    """Leaf shapes of the gradient of ``n_layers`` of ``cfg``'s layers,
    every leaf at its published widths."""
    from repro.models import registry
    return [jax.ShapeDtypeStruct((n_layers,) + s.shape[1:], s.dtype)
            for s in jax.tree_util.tree_leaves(
                registry.abstract_params(cfg)["layers"])]


def reduce_program(shapes, seed: int, mesh, method: str):
    """Per device of ``mesh``'s "data" axis, draw N(0, 1) gradients of
    ``shapes``, reduce them with ``method`` and with stock pmean, and
    return the squared L2 norms of the difference and of the stock result
    (the jitted program)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel import collectives, compat

    def per_device(i):
        key = jax.random.fold_in(jax.random.key(seed), i[0])
        g = [jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
             for k, s in zip(jax.random.split(key, len(shapes)), shapes)]
        stock, _ = collectives.reduce_gradients(g, "data", "stock")
        int8, _ = collectives.reduce_gradients(g, "data", method)
        num = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                  for a, b in zip(int8, stock))
        den = sum(jnp.sum(jnp.square(b.astype(jnp.float32))) for b in stock)
        return num[None], den[None]

    return jax.jit(compat.shard_map(per_device, mesh, in_specs=P("data"),
                                    out_specs=(P("data"), P("data"))))


def reduce_check(cfg, seed: int, clock: CompileClock,
                 method: str = "int8_a2a") -> None:
    """``reduce_gradients`` over a gradient tree shaped like
    ``REDUCE_LAYERS`` of ``cfg``'s layers on a 4-device data mesh:
    ``method`` against stock pmean.  Each device draws its own N(0, 1)
    gradients; only two scalars leave the program."""
    from repro.parallel import compat
    n = 4
    shapes = gradient_shapes(cfg, REDUCE_LAYERS)
    f = reduce_program(shapes, seed, compat.make_mesh((n,), ("data",)),
                       method)
    n_params = sum(math.prod(s.shape) for s in shapes)
    t0 = time.perf_counter()
    num, den = jax.block_until_ready(f(jnp.arange(n, dtype=jnp.int32)))
    wall = time.perf_counter() - t0
    compile_s, n_compiles = clock.take()
    rel = math.sqrt(float(num[0]) / float(den[0]))
    print(f"[smoke] reduce_gradients {method} vs stock over {len(shapes)} "
          f"leaves, {n_params} params per device, {n} devices: relative L2 "
          f"error {rel:.6g} (compile {compile_s:.3f}s over {n_compiles} "
          f"programs, call {wall:.3f}s)")
    check(rel <= REDUCE_TOL, f"int8 reduce within {REDUCE_TOL} of stock")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python chip_smoke.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: paged serve + checks; 4: tp=4 vs tp=1 serve "
                         "and int8 vs stock gradient reduce")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random params, prompts and probes")
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{platform!r} ({len(devices)} device(s))")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices; JAX found {len(devices)}")
    print(f"[smoke] {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {runtime.enable_compile_cache()}")
    if args.chips == 1:
        one_chip(args.seed)
    else:
        four_chips(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
