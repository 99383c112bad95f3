"""The single entry point for running paper characterizations.

The Runner walks selected registry specs, enforces declared requirements,
stamps wall-clock metadata on every Record, persists the Record stream,
and keeps error Records separate so callers can exit nonzero — the seed's
``benchmarks/run.py`` swallowed exceptions into a CSV row and always
exited 0.

SKIP vs ERROR semantics (the stress-ng convention, see also
``registry``): an experiment whose **declared** requirement is unmet
(``requires_devices`` > available) is never called — the Runner emits one
Record with ``skipped=True`` and a human-readable ``reason``.  SKIPs are
informational and leave ``RunReport.ok`` True.  An exception *escaping* an
experiment becomes a Record with ``error=True``; errors flip ``ok`` and
the CLI exit status.  Records an experiment yields itself (including its
own skip rows) pass through unchanged apart from ``stamp()``.

Persistence: unless ``records_dir=None``, every run streams its Records
to ``<records_dir>/run-<timestamp>-<pid>-<seq>.jsonl`` (default
``experiments/records/``) as they are produced — a crash mid-run leaves
the rows measured so far on disk.  Every emitted Record is stamped with
the producing git commit (``params["git_commit"]``, when a repo is
reachable) so a persisted stream identifies its code version.
``RunReport.records_path`` names the file; ``python -m repro.experiments
diff old.jsonl new.jsonl [--threshold METRIC=[+|-]REL]`` compares two
such streams and can gate on per-metric, direction-aware noise thresholds
(see ``repro.experiments.diff``).
"""
from __future__ import annotations

import itertools
import os
import subprocess
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

from repro.experiments import record as rec
from repro.experiments import registry as reg
from repro.experiments.record import Record

DEFAULT_RECORDS_DIR = os.path.join("experiments", "records")

_RUN_SEQ = itertools.count()   # disambiguates same-second runs in-process


@dataclass
class RunReport:
    records: list[Record] = field(default_factory=list)
    errors: list[Record] = field(default_factory=list)   # subset of records
    skips: list[Record] = field(default_factory=list)    # subset of records
    records_path: Optional[str] = None   # persisted JSONL stream, if any

    @property
    def ok(self) -> bool:
        return not self.errors

    def by_experiment(self, name: str) -> list[Record]:
        return [r for r in self.records if r.experiment == name]


def _git_commit() -> Optional[str]:
    """The commit of the checkout this code runs from, or None when it is
    not a git repo / git is unavailable.

    Resolved against this file's directory, NOT the process cwd — a run
    launched from inside some other repository must not stamp Records with
    that repo's HEAD.  Every Record a Runner emits carries the sha
    (``params["git_commit"]``) so a persisted stream identifies the code
    that produced it — the regression-diff CI job keys on this."""
    try:
        p = subprocess.run(["git", "rev-parse", "HEAD"],
                           capture_output=True, text=True, timeout=10,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
    except Exception:
        return None
    sha = p.stdout.strip()
    return sha if p.returncode == 0 and sha else None


def _environment() -> dict:
    """Uniform environment stamp every emitted Record carries
    (``params["env"]``): the JAX backend, device kind and count, host
    platform and hostname.  ``diff`` refuses to gate thresholds across
    rows whose (backend, platform) differ — a CPU-vs-TPU "regression" is
    a comparison error, not a regression (``--ignore-env`` overrides).
    A backend that fails to initialize raises here: a run never records
    a device it could not see."""
    import platform
    import sys as _sys

    import jax
    devices = jax.devices()
    return {"backend": jax.default_backend(),
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "platform": _sys.platform, "hostname": platform.node()}


class Runner:
    """Run registered experiments and emit the unified Record stream.

    ``records_dir`` is where the per-run JSONL stream lands (created on
    demand); pass ``None`` to disable persistence (unit tests, dry probes).
    """

    def __init__(self, duration: float = 0.25,
                 only: Optional[Iterable[str]] = None,
                 load_builtin: bool = True,
                 records_dir: Optional[str] = DEFAULT_RECORDS_DIR):
        if load_builtin:
            reg.load_builtin()
        self.duration = duration
        self.specs = reg.select(only)
        self.records_dir = records_dir

    def _open_stream(self):
        """(path, fh) for this run's JSONL stream, or (None, None)."""
        if not self.records_dir:
            return None, None
        os.makedirs(self.records_dir, exist_ok=True)
        name = (f"run-{time.strftime('%Y%m%d-%H%M%S')}"
                f"-{os.getpid()}-{next(_RUN_SEQ)}.jsonl")
        path = os.path.join(self.records_dir, name)
        return path, open(path, "w")

    def run(self, emit: Optional[Callable[[Record], None]] = None,
            verbose: bool = False) -> RunReport:
        report = RunReport()
        env = _environment()
        ndev = env["device_count"]
        commit = _git_commit()
        report.records_path, stream = self._open_stream()

        def out(r: Record) -> Record:
            if commit is not None:
                r.params.setdefault("git_commit", commit)
            r.params.setdefault("env", dict(env))
            report.records.append(r)
            if r.error:
                report.errors.append(r)
            if r.skipped:
                report.skips.append(r)
            if stream:
                stream.write(r.to_json() + "\n")
                stream.flush()   # crash mid-run keeps the rows so far
            if emit:
                emit(r)
            return r

        try:
            for spec in self.specs:
                t0 = time.perf_counter()
                if ndev < spec.requires_devices:
                    out(rec.skip(spec.name,
                                 f"needs >= {spec.requires_devices} devices, "
                                 f"have {ndev}").stamp(t0))
                    continue
                # pull records manually so only *experiment* exceptions
                # become ERROR rows — a failing emit callback (closed pipe,
                # full disk) propagates to the caller instead of being
                # misattributed to the experiment under measurement
                try:
                    it = iter(spec.fn(duration=self.duration))
                except Exception as e:
                    if verbose:
                        traceback.print_exc()
                    out(rec.failure(spec.name, e).stamp(t0))
                    continue
                while True:
                    try:
                        r = next(it)
                    except StopIteration:
                        break
                    except Exception as e:
                        if verbose:
                            traceback.print_exc()
                        out(rec.failure(spec.name, e).stamp(t0))
                        break
                    out(r.stamp(t0))
        finally:
            if stream:
                stream.close()
        return report


def run_experiments(duration: float = 0.25,
                    only: Optional[Iterable[str]] = None,
                    records_dir: Optional[str] = DEFAULT_RECORDS_DIR
                    ) -> RunReport:
    """One-call convenience wrapper used by examples and benchmarks."""
    return Runner(duration=duration, only=only,
                  records_dir=records_dir).run()
