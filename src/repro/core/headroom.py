"""Processing-headroom characterization — the pktgen delay-sweep analogue.

The paper's method (section II): drive the link at full rate, inject an
artificial per-burst delay, and find the maximum delay the device absorbs
before throughput drops; that delay (minus the no-delay burst time) is the
headroom available for offloaded computation.

Two modes:

* **Measured** (runs on this container's CPU backend, and unchanged on a
  real TPU): ``transfer_sweep`` maps throughput vs message size / workers
  (Fig. 1/3); ``delay_sweep`` injects synthetic compute into the jitted
  transfer step and finds the knee (Fig. 2/4).  Both emit the unified
  ``Record`` schema and time through the shared ``experiments.measure``
  harness.

* **Derived** (from the dry-run roofline): ``derived_headroom`` converts a
  cell's (compute, memory, collective) seconds into the headroom available
  while the dominant resource is saturated — how many FLOPs of offloaded
  work the step absorbs for free (the "22.8% CPU time" analogue).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.analysis import peaks
from repro.experiments.measure import measure
from repro.experiments.record import Record


# ---------------------------------------------------------------------------
# measured mode
# ---------------------------------------------------------------------------

def transfer_sweep(message_bytes: list[int], workers: list[int],
                   duration: float = 0.3,
                   experiment: str = "headroom.transfer") -> list[Record]:
    """Throughput (GB/s) of a streaming 'transfer' vs message size & workers.

    The transfer proxy is an HBM-rate stream op per worker buffer (on a real
    deployment this is the ICI/DCN send; the shape of the curve — small
    messages can't fill the pipe — is the object of study, as in Fig. 1/3)."""
    records = []
    for w in workers:
        for nbytes in message_bytes:
            n = max(nbytes // 4, 1)
            bufs = [jnp.ones((n,), jnp.float32) for _ in range(w)]
            f = jax.jit(lambda *xs: [x * 2.0 + 1.0 for x in xs])
            m = measure(lambda: f(*bufs), duration)
            records.append(Record(
                experiment, f"w{w}_m{nbytes}", "gbytes_per_sec",
                m.calls_per_sec * nbytes * w * 2 / 1e9, unit="GB/s",
                params={"workers": w, "message_bytes": nbytes,
                        "ops_per_sec": m.calls_per_sec,
                        "median_s": m.median_s, "p90_s": m.p90_s}))
    return records


def delay_sweep(message_bytes: int, matmul_sizes: list[int],
                duration: float = 0.3, tol: float = 0.10,
                experiment: str = "headroom.delay_sweep") -> list[Record]:
    """Inject synthetic offloaded compute into the transfer step (Fig. 2/4).

    Emits one Record per injected-compute size (metric ``relative`` — the
    throughput fraction of baseline) and summary Records for the knee (the
    largest size staying within ``1 - tol`` of baseline) and the implied
    headroom seconds per burst."""
    n = max(message_bytes // 4, 1)
    buf = jnp.ones((n,), jnp.float32)

    base_f = jax.jit(lambda x: x * 2.0 + 1.0)
    base = measure(lambda: base_f(buf), duration).calls_per_sec
    records = [Record(experiment, "matmul0", "ops_per_sec", base,
                      unit="ops/s", relative=1.0, params={"matmul": 0})]
    knee, headroom_s = 0, 0.0
    for m in matmul_sizes:
        w = jnp.ones((m, m), jnp.float32)
        f = jax.jit(lambda x, w: (x * 2.0 + 1.0, w @ w))
        thr = measure(lambda: f(buf, w), duration).calls_per_sec
        rel = thr / base
        records.append(Record(experiment, f"matmul{m}", "ops_per_sec", thr,
                              unit="ops/s", relative=rel,
                              params={"matmul": m}))
        if rel >= 1.0 - tol:
            knee = m
            # injected work absorbed per burst, in seconds
            headroom_s = max(headroom_s, 1.0 / thr - 1.0 / base)
    headroom_s = max(headroom_s, 0.0)
    records.append(Record(experiment, "knee", "matmul_size", knee,
                          params={"tol": tol}))
    records.append(Record(experiment, "headroom", "s_per_burst", headroom_s,
                          unit="s"))
    records.append(Record(experiment, "headroom", "fraction",
                          headroom_s * base))
    return records


def sweep_summary(records: list[Record]) -> dict:
    """Pull the delay-sweep summary values back out of the Record stream."""
    by = {(r.name, r.metric): r for r in records}
    return {
        "baseline_ops_per_sec": by[("matmul0", "ops_per_sec")].value,
        "knee_matmul": by[("knee", "matmul_size")].value,
        "headroom_s_per_burst": by[("headroom", "s_per_burst")].value,
        "headroom_fraction": by[("headroom", "fraction")].value,
    }


# ---------------------------------------------------------------------------
# derived mode (from dry-run roofline terms)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def bottleneck(self) -> str:
        vals = {"compute": self.compute_s, "memory": self.memory_s,
                "collective": self.collective_s}
        return max(vals, key=vals.get)

    @property
    def step_s(self) -> float:
        """Perfect-overlap step-time model: the dominant term."""
        return max(self.compute_s, self.memory_s, self.collective_s)


def derived_headroom(t: RooflineTerms,
                     device_kind: str = peaks.DRYRUN_KIND) -> dict:
    """Headroom while the dominant resource is saturated (the paper's Q1).

    When the step is collective-bound, compute sits idle for
    (collective - compute) seconds — offloaded transforms (compression,
    checksums, re-quantization) are FREE up to that budget.  Mirrors the
    paper's max-delay-per-burst: delay_max = T_dominant, burst time =
    T_compute, headroom = delay_max - burst."""
    dom = t.bottleneck
    headroom_s = max(0.0, t.step_s - t.compute_s)
    return {
        "bottleneck": dom,
        "step_s": t.step_s,
        "headroom_s": headroom_s,
        "headroom_fraction": headroom_s / t.step_s if t.step_s else 0.0,
        "free_offload_gflops": (headroom_s
                                * peaks.peaks(device_kind).bf16_flops / 1e9),
        "advice": _advice(t),
    }


def _advice(t: RooflineTerms) -> str:
    dom = t.bottleneck
    if dom == "collective":
        return ("collective-bound: enable in-path compression "
                "(dp_method=int8_a2a/int8_ring) — transform rides for free "
                "in the compute headroom")
    if dom == "memory":
        return ("memory-bound: increase arithmetic intensity (fuse, larger "
                "blocks, avoid remat of matmuls) before offloading anything")
    return ("compute-bound: do NOT offload extra work into this step; "
            "paper's separated-host-mode lesson — the in-path processor "
            "is already saturated")
