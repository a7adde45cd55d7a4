"""Continuous-batching serving engine over ``BatchedSpecEngine`` (dense
slot-stacked caches) or ``PagedSpecEngine`` (global block pools + per-stream
block tables, ``paged=True``).

Scheduler model
---------------
The server owns a fixed pool of ``max_concurrency`` slots backed by ONE
cache pair and ONE jitted batched draft/verify program (compiled once per
(B, gamma_max) — admission never recompiles it).  POLICY — which request
gets a slot, when prefill runs, who gets evicted — lives in a pluggable
scheduler (``serving/scheduler.py``, docs/slo_scheduling.md):

* ``FIFOScheduler`` (default): every tick begins by prefilling queued
  requests into free slots (FIFO) until the pool is full; an admitted
  request generates in that same tick's batched session.  In-flight
  streams are never paused.  Paged mode is additionally BLOCK-AWARE:
  admission reserves the request's worst-case KV blocks (prompt + token
  budget + draft overshoot) from the shared pool, and when the
  head-of-queue request cannot be covered the scheduler BACKPRESSURES —
  the request stays queued (FIFO order intact) until completions release
  enough blocks.  Reserving worst-case up front means a running stream
  can never hit pool exhaustion mid-flight.
* ``SLOScheduler`` (paged only): priority classes + per-request deadlines
  (``priority=`` / ``slo_ticks=`` on ``submit``), chunked admission
  prefill under a per-tick token budget, and preemption of
  strictly-lower-priority streams via ``engine.preempt_stream`` — frozen
  streams resume through the prefix cache with their KV warm.
* **Slot reuse**: when a stream finishes (EOS / token budget / max_len) its
  slot is released at the end of the tick and the next queued request takes
  it over — the lane's stale cache contents are fully overwritten by the
  admission prefill.
* **Active-mask semantics**: a tick always runs the full fixed-B program;
  slots that are empty (or finished mid-tick, or still mid-chunked-prefill)
  ride along with their lane masked — their device outputs are zeroed
  (``n_drafted == n_accepted == 0``), their bandit observations are
  dropped, and their cache lanes are reconciled by the engine's batched
  rollback, so a masked slot can never perturb its neighbors.

* **Sharding** (``mesh=``, docs/sharding.md): the server hands the mesh to
  its engine, which places params (serve-mode tensor-parallel rules) and
  caches (slot lanes / paged tables over the ("pod","data") batch axes,
  pool heads over "model") at init and compiles the batched session
  programs with NamedSharding in/out shardings.  Admission prefills run
  against mesh-resident state, so a new stream's lane lands directly on
  the shard that owns its slot.

All streams share ONE TapOut controller — the bandit is online across
requests, exactly the paper's deployment setting.  Each tick yields one
batch of per-stream (arms, n_drafted, n_accepted) observations, consumed by
``controller.update_batch`` as an ORDER-INDEPENDENT merge against the
pre-tick bandit state (slot index carries no information).

Per-request accounting: queue delay (submit -> FIRST admission), latency
(submit -> completion, wall seconds AND deterministic scheduler ticks),
SLO attainment, preemption counts and per-stream session stats are
recorded on the ``Response``; ``throughput_stats`` aggregates tokens/s,
p50/p95 latency and queue delay, and per-priority tails.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.controller import Controller
from repro.core.engine import (EngineSpec, GenResult, ModelBundle,
                               engine_spec_from_legacy, make_engine)
from repro.serving.scheduler import FIFOScheduler


@dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    priority: int = 0                      # higher = more urgent
    slo_ticks: Optional[int] = None        # deadline: submitted_tick + slo
    submitted_at: float = field(default_factory=time.perf_counter)
    submitted_tick: int = 0


@dataclass
class Response:
    request_id: int
    result: GenResult
    latency_s: float
    queue_delay_s: float
    priority: int = 0
    slo_ticks: Optional[int] = None
    latency_ticks: int = 0                 # submit tick -> completion tick
    queue_delay_ticks: int = 0             # submit tick -> first admission
    slo_met: bool = True                   # latency_ticks <= slo_ticks
    n_preemptions: int = 0


_LEGACY_KWARGS = ("max_len", "max_concurrency", "temperature", "greedy",
                  "seed", "paged", "block_size", "pool_tokens", "tree",
                  "kv_dtype", "quant_draft", "mesh")


class SpecServer:
    def __init__(self, draft: ModelBundle, target: ModelBundle,
                 controller: Controller, *,
                 spec: Optional[EngineSpec] = None,
                 scheduler=None, **legacy):
        # ONE construction surface: an EngineSpec describes the whole
        # deployment (backend, concurrency, precision, placement — see
        # ``core.engine.EngineSpec`` and docs/serving.md) and the factory
        # builds the matching engine.  The pre-spec keyword surface
        # (max_concurrency=, paged=, tree=, ...) still works through
        # ``engine_spec_from_legacy`` but is deprecated.
        if spec is not None and legacy:
            raise TypeError(
                f"pass spec= OR legacy engine kwargs, not both: {sorted(legacy)}")
        if spec is None:
            unknown = set(legacy) - set(_LEGACY_KWARGS)
            if unknown:
                raise TypeError(f"unknown SpecServer kwargs: {sorted(unknown)}")
            if legacy:
                warnings.warn(
                    "SpecServer(max_concurrency=..., paged=..., tree=..., ...)"
                    " is deprecated; pass spec=EngineSpec(...) instead"
                    " (docs/serving.md has the migration table)",
                    DeprecationWarning, stacklevel=2)
            spec = engine_spec_from_legacy(**legacy)
        # serving needs a slot engine: the single-stream and B=1-tree
        # backends promote to their slot facades
        backend = spec.resolve_backend()
        backend = {"single": "batched", "tree": "tree_slot"}.get(backend,
                                                                 backend)
        self.engine = make_engine(draft, target, controller, spec,
                                  backend=backend)
        self.spec = spec
        self.backend = backend
        self.mesh = spec.mesh
        self.paged = backend == "paged"
        self.tree = backend == "tree_slot"
        self.scheduler = scheduler if scheduler is not None else FIFOScheduler()
        if getattr(self.scheduler, "requires_paged", False) and not self.paged:
            raise ValueError(
                f"scheduler {self.scheduler.name!r} needs the paged backend "
                "(chunked prefill and preemption live on block pools)")
        self.gamma_max = controller.gamma_max
        self.max_concurrency = spec.batch_size
        self.queue: deque = deque()
        self.requests: Dict[int, Request] = {}
        self.responses: List[Response] = []
        self._next_id = 0
        self._slot_rid: Dict[int, int] = {}      # slot -> request_id
        self._slot_started: Dict[int, float] = {}
        self._frozen: Dict[int, dict] = {}       # rid -> preempt handle
        self._queue_delay: Dict[int, float] = {}  # rid -> submit->1st admit
        self._admit_tick: Dict[int, int] = {}
        self._rid_preempts: Dict[int, int] = {}
        self.tick_count = 0
        self.backpressure_events = 0
        self.preemption_events = 0
        self.resume_events = 0
        self.max_prefill_tokens_per_tick = 0
        self.peak_concurrency = 0

    # ------------------------------------------------------------- api
    def submit(self, prompt: List[int], max_new_tokens: int,
               eos_id: Optional[int] = None, *, priority: int = 0,
               slo_ticks: Optional[int] = None) -> int:
        rid = self._next_id
        self._next_id += 1
        self.requests[rid] = Request(rid, prompt, max_new_tokens, eos_id,
                                     priority=priority, slo_ticks=slo_ticks,
                                     submitted_tick=self.tick_count)
        self.queue.append(rid)
        return rid

    @property
    def active(self) -> Dict[int, dict]:
        """request_id -> live stream state (monitoring view)."""
        return {rid: self.engine.slots[slot]
                for slot, rid in self._slot_rid.items()}

    def _reserve_tokens(self, rid: int) -> int:
        """Worst-case sequence length of a request: prompt + budget + the
        draft's maximum overshoot within one session.  A preempted request
        resumes from its frozen sequence with only its REMAINING token
        budget outstanding."""
        req = self.requests[rid]
        frozen = self._frozen.get(rid)
        if frozen is not None:
            remaining = max(req.max_new_tokens - frozen["res"].new_tokens, 0)
            return len(frozen["seq"]) + remaining + self.gamma_max + 2
        return len(req.prompt) + req.max_new_tokens + self.gamma_max + 2

    def can_admit(self, rid: int) -> bool:
        """Block-feasibility probe for schedulers (paged backend)."""
        frozen = self._frozen.get(rid)
        prompt = frozen["seq"] if frozen else self.requests[rid].prompt
        return self.engine.can_admit(self._reserve_tokens(rid), prompt=prompt)

    # ------------------------------------------- scheduler mechanisms
    def _open(self, slot: int, rid: int, chunked: bool = False) -> None:
        """Open (or RESUME) request ``rid`` in ``slot``.  Raises
        ``PoolExhausted`` without consuming the frozen handle, so a failed
        attempt can retry later."""
        req = self.requests[rid]
        frozen = self._frozen.get(rid)
        prompt = frozen["seq"] if frozen else req.prompt
        skipped = getattr(self.engine, "prefill_tokens_skipped", 0)
        wait_us = int((time.perf_counter() - req.submitted_at) * 1e6)
        with TraceAnnotation("server.admit", rid=rid,
                             prompt_tokens=len(prompt),
                             queue_wait_us=wait_us) as span:
            if not self.paged:
                self.engine.open_stream(slot, prompt, req.eos_id)
            else:
                opener = (self.engine.open_stream_chunked if chunked
                          else self.engine.open_stream)
                opener(slot, prompt, req.eos_id,
                       reserve_tokens=self._reserve_tokens(rid),
                       resume_from=frozen["res"] if frozen else None)
            span.set_metadata(adopted_tokens=getattr(
                self.engine, "prefill_tokens_skipped", 0) - skipped)
        if frozen is not None:
            del self._frozen[rid]
            self.resume_events += 1
        self._slot_rid[slot] = rid
        now = time.perf_counter()
        self._slot_started[slot] = now
        if rid not in self._queue_delay:       # first admission only
            self._queue_delay[rid] = now - req.submitted_at
            self._admit_tick[rid] = self.tick_count

    def _preempt(self, slot: int) -> int:
        """Freeze the stream in ``slot`` and requeue its request as
        resumable.  The engine registers the stream's computed KV in the
        prefix cache before releasing the blocks, so resume re-adopts it
        instead of recomputing."""
        rid = self._slot_rid.pop(slot)
        started = self._slot_started.pop(slot)
        frozen = self.engine.preempt_stream(slot)
        frozen["res"].wall_time_s += time.perf_counter() - started
        self._frozen[rid] = frozen
        self._rid_preempts[rid] = self._rid_preempts.get(rid, 0) + 1
        self.preemption_events += 1
        self.queue.append(rid)
        return rid

    def step(self) -> List[int]:
        """One scheduler tick, PIPELINED against the device:

          1. flush tick t-1 (read back its device-resident outcomes, do
             per-stream accounting, feed the bandit),
          2. release the slots that finished,
          3. run the scheduler (admission, chunked prefill, preemption —
             the engine's tick is fully flushed here, so preemption's
             rollback-and-release cannot race a pending device program),
          4. launch tick t (fused engines: one asynchronous device
             program; its outcomes are read by the NEXT step's flush).

        The bandit therefore consumes acceptance outcomes one step behind
        the device, but its begin/update call sequence — and so its state
        — is exactly what back-to-back synchronous ticks produce.  Returns
        the request ids that completed this tick (i.e. in the flushed
        tick t-1; several streams can finish in one tick).

        Each phase runs under a ``jax.profiler.TraceAnnotation`` named
        after what it calls, all inside ``server.step`` (docs/serving.md,
        "Tracing a server")."""
        with TraceAnnotation("server.step", active=len(self._slot_rid),
                             queued=len(self.queue)):
            with TraceAnnotation("engine.session_step_flush"):
                self.engine.session_step_flush()
            with TraceAnnotation("server.release_finished"):
                finished = self._release_finished()
            before = getattr(self.engine, "prefill_tokens_computed", None)
            with TraceAnnotation("scheduler.schedule"):
                self.scheduler.schedule(self)
            if before is not None:
                # per-tick decode stall from admission prefill (chunked
                # schedulers bound this; monolithic admission pays the
                # whole non-cached prompt suffix at once)
                self.max_prefill_tokens_per_tick = max(
                    self.max_prefill_tokens_per_tick,
                    self.engine.prefill_tokens_computed - before)
            if self._slot_rid:
                self.peak_concurrency = max(self.peak_concurrency,
                                            len(self._slot_rid))
                with TraceAnnotation("engine.session_step_launch"):
                    self.engine.session_step_launch()
            self.tick_count += 1
        return finished

    def _release_finished(self) -> List[int]:
        finished: List[int] = []
        for slot in list(self._slot_rid):
            st = self.engine.slots[slot]
            if st.get("prefilling"):
                continue
            rid = self._slot_rid[slot]
            req = self.requests[rid]
            res: GenResult = st["res"]
            if st["done"] or res.new_tokens >= req.max_new_tokens:
                now = time.perf_counter()
                started = self._slot_started.pop(slot)
                res.wall_time_s += now - started
                lat_ticks = self.tick_count - req.submitted_tick
                self.responses.append(Response(
                    rid, res, latency_s=now - req.submitted_at,
                    queue_delay_s=self._queue_delay.pop(rid),
                    priority=req.priority, slo_ticks=req.slo_ticks,
                    latency_ticks=lat_ticks,
                    queue_delay_ticks=(self._admit_tick.pop(rid)
                                       - req.submitted_tick),
                    slo_met=(req.slo_ticks is None
                             or lat_ticks <= req.slo_ticks),
                    n_preemptions=self._rid_preempts.pop(rid, 0)))
                self.engine.close_stream(slot)
                del self._slot_rid[slot]
                finished.append(rid)
        return finished

    def run_until_drained(self, max_ticks: int = 1_000_000,
                          timeout_s: Optional[float] = None
                          ) -> List[Response]:
        """Tick until every submitted request has completed (bounded by
        ``max_ticks``).  ``timeout_s`` adds a WALL-CLOCK bound: a wedged
        stream (device hang, scheduler livelock) raises ``TimeoutError``
        carrying a stuck-stream diagnostic instead of spinning silently
        for a million ticks."""
        # the loop condition naturally drains the pipeline: after the last
        # launch, _slot_rid stays non-empty until the final flush+release
        deadline = (None if timeout_s is None
                    else time.perf_counter() + timeout_s)
        ticks = 0
        while (self.queue or self._slot_rid) and ticks < max_ticks:
            if deadline is not None and time.perf_counter() > deadline:
                raise TimeoutError(
                    f"SpecServer drain exceeded timeout_s={timeout_s}\n"
                    + self._stuck_diagnostic())
            self.step()
            ticks += 1
        return self.responses

    def _stuck_diagnostic(self) -> str:
        """What is the server waiting on?  One line per live slot plus
        queue/backpressure state — enough to tell a wedged stream (done
        never set, length frozen) from pool starvation (deep queue, high
        backpressure count, no free blocks)."""
        lines = [f"tick={self.tick_count} queued={len(self.queue)} "
                 f"head={list(self.queue)[:8]} "
                 f"frozen={sorted(self._frozen)} "
                 f"backpressure_events={self.backpressure_events}"]
        for slot, rid in sorted(self._slot_rid.items()):
            st = self.engine.slots[slot]
            tag = "prefilling" if st.get("prefilling") else (
                "done" if st["done"] else "decoding")
            lines.append(
                f"  slot {slot}: rid={rid} {tag} seq_len={len(st['seq'])} "
                f"new_tokens={st['res'].new_tokens}"
                f"/{self.requests[rid].max_new_tokens}")
        if self.paged:
            lines.append(f"  pool: free_blocks="
                         f"{len(self.engine.dalloc.free)}(draft)/"
                         f"{len(self.engine.talloc.free)}(target)")
        return "\n".join(lines)

    # ------------------------------------------------------------- stats
    def throughput_stats(self) -> dict:
        if not self.responses:
            return {}
        toks = sum(r.result.new_tokens for r in self.responses)
        cost = sum(r.result.modeled_cost for r in self.responses)
        wall = sum(r.result.wall_time_s for r in self.responses)
        acc = sum(r.result.total_accepted for r in self.responses)
        drf = sum(r.result.total_drafted for r in self.responses)
        lats = np.array([r.latency_s for r in self.responses])
        qds = np.array([r.queue_delay_s for r in self.responses])
        sessions = sum(len(r.result.sessions) for r in self.responses)
        stats = {
            "n_requests": len(self.responses),
            "total_new_tokens": toks,
            "modeled_cost_per_token": cost / max(toks, 1),
            "wall_s_per_token": wall / max(toks, 1),
            "accept_rate": acc / max(drf, 1),
            # canonical across ALL backends (the tree-vs-chain objective is
            # just its specialization): accepted tokens per verify forward
            "accepted_per_verify": acc / max(sessions, 1),
            "mean_latency_s": float(lats.mean()),
            "p50_latency_s": float(np.percentile(lats, 50)),
            "p95_latency_s": float(np.percentile(lats, 95)),
            "mean_queue_delay_s": float(qds.mean()),
            "p50_queue_delay_s": float(np.percentile(qds, 50)),
            "p95_queue_delay_s": float(np.percentile(qds, 95)),
            "per_priority": self._per_priority_stats(),
            "scheduler": self.scheduler.name,
            "peak_concurrency": self.peak_concurrency,
            "backpressure_events": self.backpressure_events,
            "preemption_events": self.preemption_events,
            "resume_events": self.resume_events,
            "max_prefill_tokens_per_tick": self.max_prefill_tokens_per_tick,
            # canonical settings blob: what produced these numbers
            "engine": self.engine.describe(),
        }
        if self.mesh is not None:
            stats["mesh_devices"] = int(self.mesh.devices.size)
            stats["mesh_axes"] = {k: int(v)
                                  for k, v in self.mesh.shape.items()}
        if self.paged:
            stats.update(self.engine.pool_stats())
        if self.tree:
            # the bandit's shape preferences after serving this workload
            ctrl = self.engine.controller
            stats["shape_names"] = [s.name for s in ctrl.shapes]
            stats["shape_pulls"] = ctrl.shape_pulls.tolist()
            stats["shape_values"] = np.asarray(ctrl.arm_values).tolist()
        if getattr(self.engine, "drafters", None) is not None:
            # drafter-axis marginals: which drafter the meta-bandit pulled
            ctrl = self.engine.controller
            stats["shape_names"] = [s.name for s in ctrl.shapes]
            stats["shape_pulls"] = ctrl.shape_pulls.tolist()
            stats["drafter_names"] = self.engine.drafters.names
            stats["drafter_pulls"] = ctrl.drafter_pulls
        return stats

    def _per_priority_stats(self) -> dict:
        """Per-priority-class tails: the whole point of the SLO scheduler
        is that these DIVERGE (interactive p95 stays low while batch
        absorbs the queueing) even when the aggregate numbers match."""
        out: Dict[str, dict] = {}
        for p in sorted({r.priority for r in self.responses}):
            rs = [r for r in self.responses if r.priority == p]
            lats = np.array([r.latency_s for r in rs])
            qds = np.array([r.queue_delay_s for r in rs])
            slo = [r for r in rs if r.slo_ticks is not None]
            out[str(p)] = {
                "n_requests": len(rs),
                "p50_latency_s": float(np.percentile(lats, 50)),
                "p95_latency_s": float(np.percentile(lats, 95)),
                "p95_queue_delay_s": float(np.percentile(qds, 95)),
                "slo_met_frac": (sum(r.slo_met for r in slo) / len(slo)
                                 if slo else 1.0),
            }
        return out
