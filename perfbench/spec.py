"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one mix or one metric is a
file of its own; ``BENCHMARK.json`` names them:

- a configuration is the ``file`` its ``configs`` entry gives (sizes, the
  serving deployment, the reference that checks it, and its limits);
- a traffic mix is ``traffic/<traffic>.json`` (read by ``traffic.py``);
- a metric, end-to-end or per-layer, is read by ``metrics/<name>.py``, or
  by ``metrics/<stem>.py`` for a name ``<stem>.<group>`` split by cell
  group. A reader's ``read(run)`` returns a number, or None where the run
  holds nothing for it to read; the metric is then left out.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, _named(bench["configs"], name,
                                        "config")["file"])) as fh:
        return json.load(fh)


def traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "perfbench", "traffic",
                           f"{name}.json")) as fh:
        return json.load(fh)


def metrics(bench: dict, cell_name: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced run) or per-layer metrics
    (traced run): those listing the cell, and those listing no cells."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(name: str, root: str = ROOT):
    """The module that reads metric ``name``."""
    d = os.path.join(root, "perfbench", "metrics")
    for stem in (name, name.split(".")[0]):
        path = os.path.join(d, f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"perfbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {name!r} under {d}")
