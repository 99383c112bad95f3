"""Continuous-batching serve engine: slot admission + per-slot decode.

The static ``engine.Engine`` runs one batch to completion; this engine
keeps a fixed set of decode *slots* live and admits queued requests as
slots (and KV blocks) free, interleaving each admission's prefill with
the in-flight decode batch — a late request joins mid-stream instead of
waiting for the current batch to drain.

Mechanics (DESIGN.md section 11):

* **Per-slot caches.**  Decode caches are stacked along a leading slot
  axis over batch-1 caches, so every slot carries its *own* position
  vector — the one thing the shared-batch decode step cannot express
  (its ``index`` is a single scalar for the whole batch).  The decode
  step is ``jax.vmap`` over slots with ``in_axes=(None, 0, 0, 0)``; a
  greedy run over equal-length prompts is token-identical to the static
  engine (regression-tested).
* **Admission.**  ``SlotScheduler`` + ``KVBlockAllocator``: FIFO, a
  request is admitted only when a slot is free AND the shared block pool
  covers prompt + ``max_new_tokens`` (conservative reservation, no
  preemption).  Prefill runs batch-1 at the exact prompt length (no
  left-padding — pad tokens would attend), and its caches are written
  into the slot with one ``dynamic_update_slice`` per cache leaf.
* **Latency decomposition.**  Every request's lifecycle stamps (queue
  wait / TTFT / per-token decode) are taken on the engine clock; the
  clock is injectable (``clock=...``) so tests drive arrivals on virtual
  time and the ``serve.load_sweep`` experiment uses the wall clock.
  ``ServeRequest.token_t`` stamps the time each token reached the host.
* **Profiler spans.**  Each layer boundary of the loop is a ``serve.*``
  span on the profiler's clock (``obs.trace.annotate``): ``ingest``,
  ``admit`` (holding ``prefill``, ``first_token`` and ``insert``),
  ``decode``, ``sample``, ``book`` and ``idle``.  Spans of one request
  share ``rid``, those of one iteration ``step``; in a profile, each idle
  gap of the device falls inside the span of what the host was doing
  (DESIGN.md section 16).
* **Idle hook.**  When a loop iteration has nothing to decode or admit
  (traffic gap), ``run(..., idle_hook=...)`` invokes the hook — the
  load-sweep experiment mounts a probe kernel there and reports its
  achieved FLOP/s as the compute headroom left beside the traffic, the
  paper's question transposed to serving.

* **Tensor parallelism.**  ``tp_size=N`` (or an explicit ``mesh=``)
  routes all three cells — batch-1 prefill, vmapped slot decode, slot
  insertion — through the mesh-aware builders in ``serve/step.py``:
  params sharded by the decode rules, the per-slot KV sequence split
  over the 'model' axis, per-slot tokens/positions replicated scalars.
  The scheduler, KV allocator and the whole host loop are untouched —
  they account in slots and logical token positions, blind to device
  count — and greedy token streams stay bit-identical to the
  single-device engine (the differential tier in
  ``tests/test_serve_sharded.py`` holds them equal and pins the decode
  step's per-kind collective counts).

* **Paged KV (``paged=True``).**  The per-slot caches are replaced by the
  physical page pool of ``serve/paged.py``: the allocator's block tables
  become device arrays (one fixed-width row per slot, trash-padded), slot
  insertion scatters the prefill cache into the request's pages, and the
  decode step attends through the ragged paged-attention kernel with
  ``page_buffer_depth`` blocks of page loads in flight.  The host loop, scheduler
  and allocator decisions are IDENTICAL to the dense engine — paged is
  purely a KV-residency change — so greedy token streams stay
  bit-identical to dense at f32 (the differential tier in
  ``tests/test_serve_paged.py`` holds them equal at tp=1/2/4).

Inactive slots decode garbage (fixed shapes keep one compiled step); the
results are masked on the host and every admission overwrites the whole
slot cache, so garbage never leaks into a live request.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.configs.base import ArchConfig
from repro.obs import trace as obs_trace
from repro.obs.logbuf import BoundedLog
from repro.obs.trace import annotate
from repro.parallel import compat
from repro.serve.kv import KVBlockAllocator, blocks_for
from repro.serve.scheduler import ServeRequest, SlotScheduler
from repro.serve.step import make_continuous_cells, make_paged_cells


@dataclass(frozen=True)
class StepEvent:
    """One working engine-loop iteration, for observability (tests assert
    on it).  Idle iterations (traffic gaps) are not logged — they are
    counted in ``ContinuousEngine.idle_iters`` — so ``step_log`` growth is
    bounded by work done, not by wall time spent waiting."""
    now: float
    admitted: tuple            # rids whose prefill ran this iteration
    decoded: tuple             # rids advanced by this iteration's decode step
    queued: int                # requests still waiting after admission
    # MoE paged decode only (None otherwise): mean distinct experts the
    # step's live tokens hit per MoE layer, and the most tokens any one
    # expert took in any layer
    experts_hit: Optional[float] = None
    expert_load_max: Optional[int] = None


class ContinuousEngine:
    """Slot-based continuous batching over the family decode step.

    ``n_slots`` is the decode batch width; ``cache_len`` the per-slot KV
    capacity; ``block_size``/``kv_blocks`` configure the shared block
    pool (default: exactly enough blocks to cover every slot, so memory
    admission binds only when configured tighter than the slots).
    """

    IDLE_SLEEP_S = 5e-4   # traffic-gap wait when no idle_hook is mounted:
    #                       well under a decode step, so arrival latency
    #                       stays negligible while the loop stops spinning

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int = 4,
                 cache_len: int = 128, block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefill_per_step: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 fabric=None, mesh=None, tp_size: int = 1,
                 paged: bool = False, page_buffer_depth: int = 2,
                 slo=None, tracer=None, log_cap: Optional[int] = None,
                 debug: bool = False):
        # fabric: an optional repro.fabric.ServeFabric — the degraded-wire
        # enforcement point for serving.  Its stall_admit runs before each
        # admitted prefill (TTFT inflates, queue_wait does not) and
        # stall_decode inside each decode tick's timing window (TPOT
        # inflates).  None or a clean condition changes nothing: token
        # streams stay bit-identical (guarded in tier-1).  Both hooks are
        # host-side, so they compose unchanged with a sharded engine — a
        # straggler drags the whole tensor-parallel step.
        #
        # mesh / tp_size: tensor-parallel decode.  ``tp_size=N`` builds a
        # (1, N) ("data", "model") mesh over the visible devices; an
        # explicit ``mesh=`` wins when given.
        #
        # slo: an optional scheduler.SLOPolicy — admission goes
        # priority-aware with shed + preemption (DESIGN.md section 15).
        # None keeps exact FIFO.  Swappable between runs via
        # ``engine.scheduler.slo``.
        #
        # paged / page_buffer_depth: physical paged-KV serving (module
        # docstring).  debug=True re-checks the allocator invariants on
        # every slot recycle (KVBlockAllocator.check) — cheap at serve
        # scale, and it catches table corruption at the step that caused
        # it rather than at teardown.
        #
        # tracer: repro.obs span tracing — None resolves via the
        # ``obs_trace`` runtime knob, then the thread-local current tracer
        # (CLI --trace-out), then the disabled null tracer.  Every engine
        # emission passes a timestamp the loop already computed (the
        # virtual-clock contract: a traced run makes exactly the same
        # clock calls as an untraced one, so token streams stay
        # bit-identical — DESIGN.md section 16).  log_cap ring-buffers
        # step_log and the scheduler's admit/shed logs (evictions counted
        # in each log's ``dropped``); None keeps them unbounded.
        self.cfg = cfg
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.clock = clock
        self.paged = bool(paged)
        self.debug = bool(debug)
        self.fabric = fabric if fabric is not None \
            and not fabric.is_clean else None
        if mesh is None and tp_size > 1:
            n_dev = len(jax.devices())
            if tp_size > n_dev:
                raise ValueError(
                    f"tp_size={tp_size} exceeds the {n_dev} visible "
                    f"device(s); fabricate more with "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
            mesh = compat.make_mesh((1, tp_size), ("data", "model"))
        if kv_blocks is None:
            kv_blocks = n_slots * blocks_for(cache_len, block_size)
        if self.paged:
            # pool pages = allocatable blocks + the trash page the padded
            # table rows point at (serve/kv.py)
            self.cells = make_paged_cells(
                cfg, n_slots, cache_len, block_size, kv_blocks + 1,
                mesh=mesh, buffer_depth=page_buffer_depth)
        else:
            self.cells = make_continuous_cells(cfg, n_slots, cache_len,
                                               mesh=mesh)
        self.tp_size = self.cells.tp_size
        self.params = self.cells.put_params(params)
        # n_shards frames the allocator's placement() view only — every
        # admission decision stays in logical positions, device-blind
        self.kv = KVBlockAllocator(n_blocks=kv_blocks,
                                   block_size=block_size,
                                   n_shards=self.tp_size)
        self.tracer = tracer if tracer is not None \
            else obs_trace.resolve(clock=clock)
        self.log_cap = log_cap
        self.scheduler = SlotScheduler(n_slots, self.kv, slo=slo,
                                       tracer=self.tracer, log_cap=log_cap)
        if prefill_per_step is None:
            prefill_per_step = int(runtime.policy()["serve_prefill_per_step"])
        self.prefill_per_step = max(1, prefill_per_step)
        self.step_log: BoundedLog = BoundedLog(log_cap)
        self.idle_iters = 0
        # trace bookkeeping: which slot tracks have an open request span,
        # and whether a merged idle span is open on the engine track
        self._slot_open = [False] * n_slots
        self._idle_open = False
        self._t0 = 0.0

        self._prefill = self.cells.prefill
        self._decode = self.cells.decode
        self._insert = self.cells.insert
        if self.paged:
            self._pool = self.cells.init_pool()
            self._tables_np = np.full(
                (n_slots, self.cells.max_pages), self.kv.trash_page,
                np.int32)
            self._tables_dev = jnp.asarray(self._tables_np)
        else:
            self._caches = self.cells.init_slot_caches()
        self._tok = np.zeros((n_slots,), np.int32)
        self._idx = np.zeros((n_slots,), np.int32)

    # -- submission --------------------------------------------------------

    def _validate(self, req: ServeRequest) -> None:
        if req.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {req.max_new_tokens}")
        lifetime = len(req.prompt) + req.max_new_tokens
        if lifetime > self.cache_len:
            raise ValueError(
                f"request needs {lifetime} cache positions "
                f"(prompt {len(req.prompt)} + {req.max_new_tokens} new), "
                f"engine cache_len is {self.cache_len}")
        if self.kv.blocks_for(lifetime) > self.kv.n_blocks:
            raise ValueError(
                f"request needs {self.kv.blocks_for(lifetime)} KV blocks, "
                f"pool holds {self.kv.n_blocks}")

    # -- tracing helpers ---------------------------------------------------
    # Timestamps handed to the tracer are absolute (run epoch + relative
    # engine time): one tracer can span calibration + sweep runs and every
    # track's timestamps stay monotone in the export.

    def _T(self, rel: float) -> float:
        return self._t0 + rel

    def _trace_work_start(self, rel: float) -> None:
        """Close the merged idle span (if open) at this working
        iteration's start — consecutive idle iterations render as one
        span, ended the moment work resumes."""
        if self._idle_open:
            self.tracer.end("engine", t=self._T(rel))
            self._idle_open = False

    # -- engine steps ------------------------------------------------------

    def _admit_one(self, now: float) -> Optional[int]:
        """Admit + prefill the scheduler's next pick, if admissible.

        An SLO admission may preempt active slots to make room: each
        victim's slot is reset here (token/index zeroed; paged tables
        re-pointed at the trash page) BEFORE the new prefill lands — the
        victim's pages went back to the pool, and its old slot may stay
        free while the candidate lands elsewhere, so without the reset
        its garbage decode could scribble a page the pool re-issued.
        """
        n_preempt = len(self.scheduler.preempt_log)
        adm = self.scheduler.admit(now)
        for _, vacated in self.scheduler.preempt_log[n_preempt:]:
            self._reset_slot(vacated, t_rel=now)
        if adm is None:
            return None
        slot, req = adm
        tr = self.tracer
        stall_s = 0.0
        if tr.enabled:
            self._trace_work_start(now)
            tr.begin("engine", "admit", "engine", t=self._T(now),
                     rid=req.rid, slot=slot, prompt_len=len(req.prompt))
            self._slot_open[slot] = True
            tr.begin(f"slot{slot}", f"r{req.rid}", "slot", t=self._T(now),
                     rid=req.rid, prompt_len=len(req.prompt),
                     max_new=req.max_new_tokens, priority=req.priority)
        if self.fabric is not None:
            # admission stall lands after the scheduler stamped t_admit:
            # the injected delay shows up as prefill time / TTFT, not as
            # queue wait — the decomposition keeps blaming the fabric,
            # not the admission policy
            s0 = self.fabric.stalled_s["admit"]
            self.fabric.stall_admit()
            stall_s = self.fabric.stalled_s["admit"] - s0
            if tr.enabled and stall_s > 0:
                # span duration is the injected stall itself (measured as
                # the fabric's accumulator delta — no clock calls)
                tr.begin("engine", "fabric_stall", "fabric", t=self._T(now),
                         kind="admit", condition=self.fabric.condition.name)
                tr.end("engine", t=self._T(now + stall_s), stalled_s=stall_s)
        if tr.enabled:
            tr.begin("engine", "prefill", "engine",
                     t=self._T(now + stall_s), rid=req.rid)
        with annotate("serve.admit", rid=req.rid, slot=slot,
                      prompt_tokens=len(req.prompt)):
            with annotate("serve.prefill"):
                logits, slot_caches = self._prefill(
                    self.params, jnp.asarray(req.prompt, jnp.int32)[None])
            with annotate("serve.first_token"):
                first = int(jnp.argmax(logits[0, -1]))
            with annotate("serve.insert"):
                if self.paged:
                    # the request's pages, trash-padded to the fixed table
                    # width; insertion scatters the whole prefill cache
                    # into them
                    row = np.asarray(
                        self.kv.padded_table(req.rid, self.cells.max_pages),
                        np.int32)
                    self._pool = self._insert(self._pool, slot_caches,
                                              jnp.asarray(row))
                    self._tables_np[slot] = row
                    self._tables_dev = jnp.asarray(self._tables_np)
                else:
                    self._caches = self._insert(self._caches, slot_caches,
                                                jnp.int32(slot))
        self._tok[slot] = first
        self._idx[slot] = len(req.prompt)
        req.generated.append(first)
        req.t_first_token = self.clock() - self._t0
        req.token_t.append(req.t_first_token)
        if tr.enabled:
            # clamp against the synthetic stall extent so the engine track
            # stays monotone even when a virtual clock's tick is smaller
            # than the injected stall
            t_end = max(req.t_first_token, now + stall_s)
            tr.end("engine", t=self._T(t_end))          # prefill
            tr.instant("engine", "insert", "engine", t=self._T(t_end),
                       rid=req.rid, slot=slot, paged=self.paged)
            tr.end("engine", t=self._T(t_end), rid=req.rid)   # admit
        if len(req.generated) >= req.max_new_tokens:
            self.scheduler.complete(slot, req.t_first_token)
            self._reset_slot(slot, t_rel=max(req.t_first_token,
                                             now + stall_s))
        return req.rid

    def _decode_once(self, step: int):
        """Dispatch one decode step for every active slot and wait for its
        tokens; ``_book`` then hands them to the requests."""
        active = self.scheduler.active()
        t_start = self.clock() - self._t0
        tr = self.tracer
        stall_s = 0.0
        if tr.enabled:
            self._trace_work_start(t_start)
            tr.begin("engine", "decode", "engine", t=self._T(t_start),
                     n_active=len(active))
        if self.fabric is not None:
            # inside the tick's timing window, so per-token stamps (TPOT)
            # absorb the injected delay; the straggler term applies here —
            # a batched step moves at the pace of its slowest device
            s0 = self.fabric.stalled_s["decode"]
            self.fabric.stall_decode()
            stall_s = self.fabric.stalled_s["decode"] - s0
            if tr.enabled and stall_s > 0:
                tr.begin("engine", "fabric_stall", "fabric",
                         t=self._T(t_start), kind="decode",
                         condition=self.fabric.condition.name)
                tr.end("engine", t=self._T(t_start + stall_s),
                       stalled_s=stall_s)
        load = None
        with annotate("serve.decode", step=step, active=len(active)):
            if self.paged:
                logits, self._pool, *moe_load = self._decode(
                    self.params, jnp.asarray(self._tok)[:, None],
                    jnp.asarray(self._idx), self._pool, self._tables_dev)
                load = moe_load[0] if moe_load else None
            else:
                logits, self._caches = self._decode(
                    self.params, jnp.asarray(self._tok)[:, None, None],
                    jnp.asarray(self._idx), self._caches)
        with annotate("serve.sample", step=step):
            last = logits[:, 0] if self.paged else logits[:, 0, -1]
            nxt = jnp.argmax(last, axis=-1)
            if load is None:
                nxt = np.asarray(nxt)                       # host sync
            else:
                # the expert counts ride the tokens' transfer
                nxt, load = jax.device_get((nxt, load))
        now = self.clock() - self._t0
        return (active, nxt, t_start, now, max(now, t_start + stall_s),
                load)

    def _book(self, active, nxt, t_start: float, now: float,
              t_end: float) -> list[int]:
        """Append a decode step's tokens to their requests; complete and
        reset the slots that are done; close the step's engine-track span
        that ``_decode_once`` opened."""
        decoded = []
        for slot, req in active:
            tok = int(nxt[slot])
            req.generated.append(tok)
            req.decode_token_s.append(now - t_start)
            req.token_t.append(now)
            self._tok[slot] = tok
            self._idx[slot] += 1
            decoded.append(req.rid)
            if len(req.generated) >= req.max_new_tokens:
                self.scheduler.complete(slot, now)
                self._reset_slot(slot, t_rel=t_end)
        if self.tracer.enabled:
            self.tracer.end("engine", t=self._T(t_end),
                            n_decoded=len(decoded))
        return decoded

    def _reset_slot(self, slot: int, t_rel: Optional[float] = None) -> None:
        # keep the garbage decode of a free slot inside the cache bounds;
        # the next admission overwrites the whole slot cache anyway
        self._tok[slot] = 0
        self._idx[slot] = 0
        if self.paged:
            # the freed pages are back in the pool — point the slot's
            # table row at the trash page so its garbage decode can never
            # write into a page the next reservation hands out
            self._tables_np[slot] = self.kv.trash_page
            self._tables_dev = jnp.asarray(self._tables_np)
        if self._slot_open[slot] and t_rel is not None:
            # close the slot-track request span at the vacating event's
            # own time (complete / preempt / deadline abort)
            self.tracer.end(f"slot{slot}", t=self._T(t_rel))
            self._slot_open[slot] = False
        if self.debug:
            self.kv.check()

    def _kv_bytes(self) -> dict:
        """Bytes of the pages in use, by what they hold (paged only):
        ``{"kv_bytes": ...}`` or ``{"latent_bytes": ...}``."""
        if not self.paged:
            return {}
        from repro.serve.paged import pool_geometry
        g = pool_geometry(self.cfg, self.kv.n_pages, self.kv.block_size)
        return {f"{g['state_kind']}_bytes": g["page_bytes"] * self.kv.n_used}

    # -- run loop ----------------------------------------------------------

    def run(self, requests: list[ServeRequest],
            idle_hook: Optional[Callable[[], None]] = None,
            deadline_s: Optional[float] = None
            ) -> list[ServeRequest]:
        """Serve ``requests`` (with ``arrival_s`` offsets) to completion.

        The loop each iteration: ingest arrivals, admit + prefill up to
        ``prefill_per_step`` queued requests, run one decode step for the
        active slots — prefill interleaved with decode, not run ahead of
        it.  With nothing to decode or admit (a traffic gap) the
        ``idle_hook`` runs instead (default: a short sleep, so waiting
        for the next arrival neither pegs a core nor grows ``step_log``
        — idle iterations are counted in ``idle_iters``, not logged); the
        loop ends when every submitted request is done.  Returns
        ``requests`` in the order given.

        ``deadline_s`` bounds the run on the engine clock: at the
        deadline every unfinished request — queued, active, or not yet
        arrived — is shed with reason "deadline" (pages released, slots
        reset), which keeps overload levels of the sweeps from running
        arbitrarily past their measurement window.
        """
        if self.scheduler.n_active or self.scheduler.pending:
            raise RuntimeError(
                "engine already has requests in flight; run() is not "
                "reentrant — wait for the previous run to complete")
        for r in requests:
            self._validate(r)
        self.step_log = BoundedLog(self.log_cap)
        self.idle_iters = 0
        arrivals = sorted(requests, key=lambda r: r.arrival_s)
        n_seen = 0
        self._t0 = self.clock()
        tr = self.tracer
        if tr.enabled:
            # the scheduler shares this run's epoch so its decision
            # instants land on the same absolute timeline
            self.scheduler.trace_t0 = self._t0
            tr.instant("engine", "run_begin", "engine", t=self._t0,
                       n_requests=len(requests), n_slots=self.n_slots,
                       paged=self.paged, tp_size=self.tp_size,
                       condition=(self.fabric.condition.name
                                  if self.fabric is not None else "clean"))
            if self.paged:
                from repro.serve.paged import pool_geometry
                tr.instant("kv", "pool_geometry", "kv", t=self._t0,
                           **pool_geometry(self.cfg, self.kv.n_pages,
                                           self.kv.block_size))
        self._idle_open = False
        now = 0.0
        while n_seen < len(arrivals) or self.scheduler.has_work:
            now = self.clock() - self._t0
            if deadline_s is not None and now >= deadline_s:
                if tr.enabled:
                    self._trace_work_start(now)
                    tr.instant("engine", "deadline_abort", "engine",
                               t=self._T(now), deadline_s=deadline_s)
                for slot in self.scheduler.abort(now, reason="deadline"):
                    self._reset_slot(slot, t_rel=now)
                for r in arrivals[n_seen:]:     # never even arrived
                    r.t_shed, r.shed_reason = now, "deadline"
                n_seen = len(arrivals)
                break
            due = n_seen
            while due < len(arrivals) and arrivals[due].arrival_s <= now:
                due += 1
            if due > n_seen:
                with annotate("serve.ingest", n=due - n_seen):
                    for r in arrivals[n_seen:due]:
                        self.scheduler.submit(r, now)
                n_seen = due
            admitted = []
            for _ in range(self.prefill_per_step):
                rid = self._admit_one(self.clock() - self._t0)
                if rid is None:
                    break
                admitted.append(rid)
            step = len(self.step_log) + self.step_log.dropped
            sampled = self._decode_once(step) if self.scheduler.n_active \
                else None
            if not admitted and sampled is None:
                self.idle_iters += 1
                if tr.enabled:
                    if not self._idle_open:
                        tr.begin("engine", "idle", "engine", t=self._T(now))
                        self._idle_open = True
                    tr.metrics.count("idle_iters")
                with annotate("serve.idle"):
                    if idle_hook is not None:
                        idle_hook()
                    else:
                        time.sleep(self.IDLE_SLEEP_S)
                continue
            with annotate("serve.book", step=step):
                decoded = self._book(*sampled[:-1]) if sampled else []
                load = sampled[-1] if sampled else None
                hit = load_max = None
                if load is not None:
                    per_layer = load.reshape(-1, load.shape[-1])
                    hit = float(np.mean(np.count_nonzero(per_layer, -1)))
                    load_max = int(load.max())
                if tr.enabled:
                    # per-iteration pool/queue watermarks, each on its own
                    # counter track (timestamps are this iteration's
                    # loop-top time, monotone per track by construction)
                    tr.counter("queue", "queue_depth", t=self._T(now),
                               depth=len(self.scheduler.pending))
                    tr.counter("slots", "slot_occupancy", t=self._T(now),
                               active=self.scheduler.n_active)
                    tr.counter("kv", "kv_pages", t=self._T(now),
                               free=self.kv.n_free, used=self.kv.n_used,
                               **self._kv_bytes())
                    if load is not None:
                        tr.counter("moe", "expert_load", t=self._T(now),
                                   experts_hit=hit, load_max=load_max)
                    tr.metrics.count("work_iters")
                self.step_log.append(StepEvent(
                    now=now, admitted=tuple(admitted),
                    decoded=tuple(decoded),
                    queued=len(self.scheduler.pending),
                    experts_hit=hit, expert_load_max=load_max))
        if tr.enabled:
            # a still-open merged idle span (the loop drained while idle)
            # closes at the last loop-top time seen
            self._trace_work_start(now)
        return requests

    def generate(self, requests: list[ServeRequest]) -> list[ServeRequest]:
        """Static-API convenience: all requests arrive at t=0."""
        for r in requests:
            r.arrival_s = 0.0
        return self.run(requests)
