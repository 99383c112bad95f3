"""Fault-tolerant training loop: checkpoint/restart, straggler detection,
elastic resize.

Failure model (single-process stand-in for a 1000-node fleet):
  * a step may raise (injected via ``fault_hook`` in tests, real preemption
    in production) -> restore from the last committed checkpoint and replay;
    the data pipeline is position-keyed so replays are bit-deterministic.
  * per-step wall times feed a running z-score straggler detector — on a
    real fleet this is where slow hosts get reported to the scheduler.
  * restarting with a different mesh reshards the checkpoint on load
    (CheckpointManager.restore with new shardings).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, synth_batch
from repro.obs import trace as obs_trace


@dataclass
class LoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 20
    log_every: int = 10
    straggler_zscore: float = 3.0
    max_restarts: int = 3


@dataclass
class StragglerStats:
    times: list = field(default_factory=list)

    def observe(self, dt: float) -> Optional[str]:
        self.times.append(dt)
        if len(self.times) < 10:
            return None
        arr = np.array(self.times[-100:])
        mu, sd = arr.mean(), arr.std() + 1e-9
        z = (dt - mu) / sd
        if z > 3.0:
            return (f"straggler step: {dt*1e3:.1f}ms vs mean {mu*1e3:.1f}ms "
                    f"(z={z:.1f}) — would report host for exclusion")
        return None


def train_loop(step_fn: Callable, state, data_cfg: DataConfig,
               batch_shardings, manager: CheckpointManager,
               loop: LoopConfig, start_step: int = 0,
               fault_hook: Optional[Callable[[int], None]] = None,
               log: Callable[[str], None] = print):
    """Run the loop; returns (state, history).  Restores on step failure."""
    stats = StragglerStats()
    history = []
    step = start_step
    restarts = 0
    while step < loop.total_steps:
        try:
            if fault_hook is not None:
                fault_hook(step)
            batch = synth_batch(data_cfg, step)
            if batch_shardings is not None:
                batch = {k: jax.device_put(v, batch_shardings.get(k))
                         for k, v in batch.items()}
            tr = obs_trace.current()
            t0 = time.perf_counter()
            # the span brackets exactly the timed region (dispatch +
            # block); the train loop runs on the wall clock, so the
            # tracer stamping its own time here is fine (unlike the
            # serve engine's virtual-clock paths)
            with tr.span("train", "step", "train", step=step):
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            warn = stats.observe(dt)
            if warn:
                log(f"[step {step}] {warn}")
            history.append({"step": step,
                            "loss": float(metrics["loss"]),
                            "time_s": dt})
            if loop.log_every and step % loop.log_every == 0:
                log(f"[step {step}] loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            step += 1
            if loop.checkpoint_every and step % loop.checkpoint_every == 0:
                with tr.span("train", "checkpoint", "train", step=step):
                    manager.save(step, state)
        except KeyboardInterrupt:
            raise
        except Exception as e:  # preemption / injected fault
            restarts += 1
            if restarts > loop.max_restarts:
                raise
            last = manager.latest_step()
            log(f"[step {step}] FAILURE ({type(e).__name__}: {e}); "
                f"restoring from step {last} (restart {restarts})")
            if last is None:
                raise
            abstract = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            shardings = jax.tree_util.tree_map(lambda x: x.sharding, state)
            state, step = manager.restore(abstract, shardings=shardings)
    manager.wait()
    return state, history
