"""The system under test, as the benchmark drives it.

``build`` makes the serving engine the way ``repro.launch.serve`` does
(``build_engine``: ``ContinuousEngine(paged=True, tp_size=1, ...)``),
with its parameters drawn on the device by one jitted
``registry.init_params`` from the run's key. ``Recorder`` wraps the
engine's three step programs and its scheduler's ``submit`` from the
outside: it counts, records what each call was given, puts a
``jax.profiler.TraceAnnotation`` around each call, and starts and stops
the profiler inside the window. The engine's own code is not changed.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax._src.lib import _profiler

from repro.configs.base import ArchConfig
from repro.launch import serve as serve_cli
from repro.models import registry
from repro.serve.scheduler import ServeRequest


def arch(c: dict) -> ArchConfig:
    """The program's config from the configuration file's sizes."""
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in c.items() if k in names})


def seed_key(seed: int):
    """The run's key: ``jax.random.key(seed)``, with the bits above 32
    folded in (``jax.random.key`` keeps only the low 32)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, seed >> 32)
    return key


def build(c: dict, key):
    """(ArchConfig, engine) for configuration ``c``."""
    cfg = arch(c)
    params = jax.jit(registry.init_params, static_argnums=0)(cfg, key)
    s = c["serving"]
    args = argparse.Namespace(
        batch=s["n_slots"], cache_len=s["cache_len"],
        block_size=s["block_size"], fabric="clean", tp_size=1, paged=True,
        buffer_depth=s.get("buffer_depth", 2), slo=False, trace_out="",
        log_cap=0)
    return cfg, serve_cli.build_engine(args, cfg, params)


def make_requests(rows) -> list[ServeRequest]:
    return [ServeRequest(prompt=p, max_new_tokens=n, arrival_s=t)
            for t, p, n in rows]


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


class Recorder:
    """Observes an engine's prefill, insert and decode calls and its
    scheduler's submissions without changing what they return.

    Each call runs inside a ``TraceAnnotation`` whose arguments carry what
    the trace's readers need: the prompt tokens of a prefill; the active
    slots and the positions they attend (summed) of a decode step.
    ``profile=(start_s, length_s)`` traces the device from the first
    decode call at or after ``start_s`` into the window for ``length_s``
    seconds (the stop waits for that call's result). The trace is kept in
    memory (``xspace``, a serialized XSpace) and written nowhere inside
    the window, so the stop costs the window only the profiler's own
    collection."""

    def __init__(self, eng, profile=None):
        self.profile = profile
        self.t0 = None             # window start (perf_counter)
        self.lateness = []         # seconds from due time to submission
        self.traced = None         # (t_start, t_stop) in window seconds
        self.xspace = None         # the stopped trace, serialized
        self._session = None
        prefill, insert, decode = eng._prefill, eng._insert, eng._decode
        submit = eng.scheduler.submit

        def w_submit(req, now):
            if self.t0 is not None:
                self.lateness.append(now - req.arrival_s)
            return submit(req, now)

        def w_prefill(params, tokens):
            n = int(tokens.shape[1])
            with jax.profiler.TraceAnnotation("bench.prefill", tokens=n):
                return prefill(params, tokens)

        def w_insert(*a):
            with jax.profiler.TraceAnnotation("bench.insert"):
                return insert(*a)

        def w_decode(*a):
            t = self._now()
            active = [s for s, _ in eng.scheduler.active()]
            context = int((eng._idx[active] + 1).sum())
            if self.t0 is not None:
                self._maybe_start(t)
            with jax.profiler.TraceAnnotation("bench.decode",
                                              active=len(active),
                                              context=context):
                out = decode(*a)
            if self._session and t >= self.traced[0] + self.profile[1]:
                jax.block_until_ready(out)
                self.close()
            return out

        eng.scheduler.submit = w_submit
        eng._prefill, eng._insert, eng._decode = w_prefill, w_insert, w_decode

    def _now(self) -> float:
        return time.perf_counter() - (self.t0 or 0.0)

    def _maybe_start(self, t: float) -> None:
        if self.profile is None or self.traced is not None \
                or t < self.profile[0]:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # the session jax.profiler.start_trace opens, stopped without the
        # export that stop_trace writes to disk
        self._session = _profiler.ProfilerSession(opts)
        self.traced = (self._now(), None)

    def window(self):
        self.t0 = time.perf_counter()
        self.lateness = []

    def close(self) -> None:
        """Stop the trace (also one the window ended inside)."""
        if self._session:
            self.xspace = self._session.stop()
            self._session = None
            self.traced = (self.traced[0], self._now())


def warm(eng, traffic: dict, vocab: int) -> None:
    """Serve one request per prompt bucket of the mix (two new tokens
    each): every program the window will call compiles here — each
    prefill length, the insert, the decode step and their small ops."""
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(prompt=rng.integers(0, vocab, L).astype(np.int32),
                         max_new_tokens=2)
            for L in traffic["prompt_len"]["buckets"]]
    eng.run(reqs)


def peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
