"""Recompute the analytic fields of dry-run JSONs in place.

model_flops / useful_ratio / roofline_fraction / memory term are analytic
(no recompilation needed) — this lets cost-model fixes propagate to already
compiled cells.  Usage: PYTHONPATH=src python -m repro.analysis.refresh <dir>
"""
from __future__ import annotations

import glob
import json
import sys

from repro.analysis import roofline as rf
from repro.analysis.peaks import peaks
from repro.configs import all_archs
from repro.configs.base import SHAPES


def refresh_record(d: dict) -> dict:
    cfg = all_archs()[d["arch"]]
    shape = SHAPES[d["shape"]]
    mf = rf.model_flops(cfg, shape)
    d["model_flops"] = mf
    d["hlo_flops_global"] = d["flops_per_device"] * d["n_chips"]
    d["useful_ratio"] = mf / d["hlo_flops_global"] if d["hlo_flops_global"] else 0
    d["bytes_per_device"] = rf.analytic_memory_bytes(cfg, shape, d["n_chips"])
    chip = peaks(d["device_kind"])
    d["memory_s"] = d["bytes_per_device"] / chip.hbm_bytes_per_s
    terms = {"compute": d["compute_s"], "memory": d["memory_s"],
             "collective": d["collective_s"]}
    d["bottleneck"] = max(terms, key=terms.get)
    d["step_s"] = max(terms.values())
    ideal = mf / (d["n_chips"] * chip.bf16_flops)
    d["roofline_fraction"] = ideal / d["step_s"] if d["step_s"] else 0.0
    return d


def main(dirname: str):
    for f in sorted(glob.glob(f"{dirname}/*.json")):
        d = json.load(open(f))
        d = refresh_record(d)
        with open(f, "w") as fh:
            json.dump(d, fh, indent=1)
        print(f"{d['arch']:24s} {d['shape']:12s} {d['mesh']:9s} "
              f"{d['bottleneck']:11s} roofline={d['roofline_fraction']:.1%} "
              f"useful={d['useful_ratio']:.1%}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "experiments/dryrun")
