"""Continuous-batching serving front-end (DESIGN.md § Serving
front-end).

The synchronous ``VectorSearchService.query`` convoy: every query in a
B=64 batch waits for the SLOWEST traverser (steps_max ~31 vs steps_mean
~17.5 in the tracked bench), and an underfull request pads dead lanes
on top. This scheduler replaces batch-at-a-time execution with a
fixed-slot continuously-batched loop over the resumable slotted search
state (``core.search_jax.SlotState``):

  * a bounded request QUEUE admits single queries (ragged, mixed-k
    traffic — each request carries its own k and deadline);
  * each ``tick`` (1) swaps admitted queries into free slots as PURE
    DATA (``_slot_admit_jit`` — a fixed-width scatter, so admission
    never recompiles), (2) advances every live slot by up to
    ``quantum`` expansion iterations (``_slot_step_jit`` — the same
    layer-0 body the synchronous search compiles; slots are allocated
    low-first and the tick runs the smallest compiled WIDTH-LADDER
    prefix covering the highest live slot, so a lightly-loaded bank
    costs a small batch, not a full one), and (3) RETIRES
    slots whose per-query ``done`` mask latched, delivering answers
    out-of-order as queries individually converge — slot occupancy
    stays high instead of draining to the convoy;
  * per-query ADAPTIVE STEP BUDGETS: a fresh query starts at the p50
    of the observed per-query step distribution (the obs plane's
    ``phnsw_sched_slot_steps`` histogram) and unconverged queries
    escalate (budget doubling, counted on the obs plane) up to the
    static bound — the common fast query retires early, the rare deep
    one still converges exactly (bit-equal to the fixed-budget
    program: a budget-frozen slot keeps its frontier intact and
    resumes where it froze);
  * per-slot EFFECTIVE ef (``ef_eff = clamp(max(k, ef_policy)) <=
    compiled EF``) serves mixed-k traffic from one compiled program;
  * SLO-aware ADMISSION CONTROL: the queue is bounded (overflow sheds
    at submit) and deadline-expired requests shed at admission instead
    of burning slots — shed counters by reason, queue-depth and
    occupancy gauges, and escalation counters all land on the service
    registry.

Sharded backends run the vmapped per-shard twins over the stacked
ShardedDB view; retirement requires the done latch on every LIVE shard
and merges the disjoint per-shard lists host-side (stable sort: lower
shard then lower slot — the ``_merge_lists`` tie-break). Degraded mode
is the same live-mask data discipline as the resilient path: dead
shards (``ShardHealth`` when the service carries a fault policy) are
excluded from both the done gate and the merge, and completions carry
exact coverage accounting.

Zero steady-state recompiles by construction: admission, retirement,
budget escalation, epoch swaps, and kill/recover cycles are all data;
``cache_sizes()`` (= ``search_jax.slot_cache_sizes``) backs the
regression tests.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional

import dataclasses as _dc

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import search_jax as sj
from repro.core.distributed import stacked_db_view
from repro.obs import NULL_SPAN


class SchedulerUnsupported(RuntimeError):
    """The service's configuration has no slotted program (mesh
    collectives, sharded deferred re-ranking): callers fall back to
    the synchronous ``run_stream_sync``."""


@dataclass
class _Pending:
    rid: int
    k: int
    ef_eff: int
    t_submit: float
    t_sched: float                 # scheduled arrival (open-loop start)
    deadline: Optional[float]      # monotonic seconds, None = none
    q: Optional[np.ndarray]        # [D]; dropped once admitted


@dataclass
class Completion:
    """One retired query. ``ids``/``dists`` are the top-``k`` answer
    (GLOBAL ids on sharded backends). ``forced`` marks a query retired
    at the static step bound without latching ``done`` (exactly what
    the synchronous program would have returned for it). ``dist_h``
    is the exact count of full-dimension (Dist.H) rows evaluated for
    the query, the descent's and any deferred re-rank's included: the
    synchronous path's ``dist_h_evals``."""
    rid: int
    ids: np.ndarray
    dists: np.ndarray
    latency_ms: float
    steps: int
    forced: bool = False
    degraded: bool = False
    coverage: float = 1.0
    dist_h: int = 0


class StreamScheduler:
    """The continuous-batching front-end over one
    ``VectorSearchService``. Construct via ``svc.scheduler()``.

    ``ef`` is the COMPILED result width (defaults to the service's
    ef0): the largest k / effective ef any request may ask for.
    ``ef_policy`` is the per-request effective-ef floor (default
    ``min(svc.ef0, ef)``): a request gets ``ef_eff = max(k,
    ef_policy)``. ``quantum`` is expansion iterations per tick;
    ``slo_ms`` (optional) stamps a default deadline on every request;
    ``adaptive_budget=False`` pins every query to the static step
    bound (the fixed-budget A/B arm)."""

    def __init__(self, svc, *, n_slots: Optional[int] = None,
                 quantum: int = 32, max_queue: int = 512,
                 slo_ms: Optional[float] = None,
                 ef: Optional[int] = None,
                 ef_policy: Optional[int] = None,
                 adaptive_budget: bool = True):
        if svc.mesh is not None:
            raise SchedulerUnsupported(
                "the mesh collective path has no slotted program; "
                "serve via the host path or run_stream_sync")
        snap = svc.sdb if svc.sdb is not None else svc.db
        self.sharded = svc.sdb is not None
        # DEFERRED re-ranking (single-shard host path): slots traverse
        # in filter space at the WIDE pool width and the promote
        # (cascade) + Dist.H passes run batched over each tick's
        # retiring slots — the exact final blocks of the synchronous
        # deferred program, so run_stream stays bit-equal to
        # run_stream_sync. The sharded deferred merge-then-rerank is
        # not slotted yet.
        self.deferred = bool(snap.cfg.deferred_rerank
                             and snap.filter_kind != "none")
        if self.deferred and self.sharded:
            raise SchedulerUnsupported(
                "sharded deferred re-ranking merges per-shard lists "
                "before the global re-rank; serve via run_stream_sync")
        self.cascade = self.deferred and snap.filter_kind == "cascade"
        self.rm = int(snap.cfg.rerank_mult) if self.deferred else 1
        # wide = the slot list's pool multiplier: the cascade's promote
        # pool, else the re-rank pool (1 when not deferred)
        self.wide = max(int(snap.cfg.promote_mult), self.rm) \
            if self.cascade else self.rm
        self.svc = svc
        self.cfg = snap.cfg
        self.EF = int(ef or svc.ef0)
        self.EFW = self.EF * self.wide   # compiled slot list width
        self.ef_policy = int(min(ef_policy or svc.ef0, self.EF))
        self.S = int(n_slots or svc.batch)
        self.quantum = int(quantum)
        self.W = self.cfg.expand_width
        self.max_queue = int(max_queue)
        self.slo_ms = slo_ms
        self.adaptive = bool(adaptive_budget)
        self.tracer = svc.tracer
        r = svc.stats.registry
        self._g_depth = r.gauge("phnsw_sched_queue_depth",
                                "admission queue depth")
        self._g_occ = r.gauge("phnsw_sched_slot_occupancy",
                              "fraction of slots in flight")
        self._c_shed = r.counter("phnsw_sched_shed_total",
                                 "requests shed by admission control",
                                 labels=("reason",))
        self._c_esc = r.counter("phnsw_sched_escalations_total",
                                "per-query step-budget escalations")
        self._c_adm = r.counter("phnsw_sched_admitted_total",
                                "queries admitted into slots")
        self._c_ret = r.counter("phnsw_sched_retired_total",
                                "queries retired from slots")
        self.steps_hist = r.histogram(
            "phnsw_sched_slot_steps",
            "expansion steps per retired query (drives the p50 "
            "initial budget)")
        # host mirrors of the per-slot bookkeeping (the device state
        # carries only what the compiled program reads)
        self._rid_of = np.full(self.S, -1, np.int64)
        self._budget = np.zeros(self.S, np.int32)
        self._cap = np.zeros(self.S, np.int32)
        # per-slot promote-keep width (cascade: ef_eff * rerank_mult)
        self._keep = np.zeros(self.S, np.int32)
        self._meta: Dict[int, _Pending] = {}
        self._queue: Deque[_Pending] = deque()
        self._next_rid = 0
        self._escalated = False
        self._budget_stale = False     # host budgets not yet uploaded
        self._live_mask: Optional[np.ndarray] = None   # test override
        D = int(snap.high.shape[-1])
        self._D = D
        # WIDTH LADDER: slots are allocated low-first and each tick
        # runs the smallest compiled prefix covering the highest live
        # slot — a fixed set of widths, so partial occupancy neither
        # pays full-bank prices nor recompiles
        rungs = {self.S} | {w for w in range(16, self.S, 16)}
        self.rungs = sorted(rungs)
        qp_ex = svc.filt.prepare_jnp(
            jnp.zeros((self.rungs[0], D), jnp.float32))
        dbv = self._db()
        self.state = sj.make_slot_state(
            dbv, self.S, qp_ex, ef=self.EFW,
            n_shards=snap.n_shards if self.sharded else None,
            deferred=self.deferred)
        if self.sharded:
            self._offsets = np.asarray(svc.sdb.offsets, np.int64)
        # warm every compiled program, the device query prep included,
        # with a no-op admission (every pad row's slot id is out of
        # range -> dropped) and an empty step (all budgets 0 -> the
        # loop cond is false immediately); nothing is recorded, so
        # service stats stay clean
        for wd in self.rungs:
            self.state = self._admit_step_call(
                dbv, np.zeros((wd, D), np.float32),
                np.full(wd, self.S, np.int32),
                np.full(wd, self.EFW, np.int32),
                np.zeros(wd, np.int32), wd)
            self.state = self._step_call(dbv, wd)
        if self.deferred:
            # warm the retirement passes too (all-pad rows): steady
            # state then never compiles, even on the first real retire
            pad_fi = jnp.full((self.S, self.EFW), -1, jnp.int32)
            if self.cascade:
                sj._retire_promote_jit(
                    self.svc.db, self.state.qprep, pad_fi,
                    jnp.zeros((self.S,), jnp.int32))
            sj._retire_rerank_jit(self.svc.db, self.state.q_high,
                                  pad_fi)

    # -- plumbing ----------------------------------------------------------

    def _db(self):
        return stacked_db_view(self.svc.sdb) if self.sharded \
            else self.svc.db

    def _live(self) -> np.ndarray:
        """[P] live-shard mask: the service's fault-plane health when
        it has one, a test override otherwise, else all-live."""
        if not self.sharded:
            return np.ones(1, bool)
        if self.svc.health is not None:
            return ~np.asarray(self.svc.health.dead, bool)
        if self._live_mask is not None:
            return self._live_mask
        return np.ones(self.svc.sdb.n_shards, bool)

    def set_live(self, mask) -> None:
        """Degraded-mode override for tests/benches without a fault
        policy: serve from the ``mask``-live shards only."""
        self._live_mask = np.asarray(mask, bool)

    def _admit_step_call(self, dbv, q_new, slot_ids, ef_eff, budget,
                         width, span=NULL_SPAN):
        with span.child("sched.launch", program="admit_step",
                        width=width) as ls:
            nbytes = self._push_budget()
            host = (q_new, slot_ids, ef_eff, budget)
            qd, *rest = (jnp.asarray(a) for a in host)
            # the filter's query prep runs on the device, from the raw
            # queries just uploaded: no prepared table is uploaded
            with ls.child("sched.prepare", rows=width):
                qp = self.svc.filt.prepare_jnp(qd)
            ls.set(bytes=nbytes + sum(a.nbytes for a in host))
            fn = sj._slot_admit_step_sharded_jit if self.sharded \
                else sj._slot_admit_step_jit
            return fn(dbv, self.state, qd, qp, *rest, width,
                      self.quantum, self.W, self.deferred)

    def _step_call(self, dbv, width, span=NULL_SPAN):
        full = width >= self.S
        with span.child("sched.launch",
                        program="step" if full else "step_prefix",
                        width=width) as ls:
            ls.set(bytes=self._push_budget())
            if full:
                fn = sj._slot_step_sharded_jit if self.sharded \
                    else sj._slot_step_jit
                return fn(dbv, self.state, self.quantum, self.W,
                          self.deferred)
            fn = sj._slot_step_prefix_sharded_jit if self.sharded \
                else sj._slot_step_prefix_jit
            return fn(dbv, self.state, width, self.quantum, self.W,
                      self.deferred)

    def _push_budget(self) -> int:
        """Upload the host budgets an escalation changed, just before
        the next compiled call reads them; returns the bytes uploaded."""
        if not self._budget_stale:
            return 0
        self._budget_stale = False
        b = jnp.asarray(self._budget)
        if self.sharded:
            b = jnp.broadcast_to(b, self.state.budget.shape)
        self.state = _dc.replace(self.state, budget=b)
        return self._budget.nbytes

    def _static_cap(self, ef_eff: int) -> int:
        """The per-request step bound — the exact bound the synchronous
        program compiles for this effective ef."""
        if self.cfg.step_budget is not None:
            cap = self.cfg.max_steps_for_layer(0)
        else:
            cap = 4 * ef_eff + 16
        return -(-cap // self.W) * self.W

    def _initial_budget(self, ef_eff: int) -> int:
        """Start at the observed p50 step budget once telemetry exists
        (>= 64 retired queries), else the static bound."""
        cap = self._static_cap(ef_eff)
        if not self.adaptive or self.steps_hist.count < 64:
            return cap
        b = int(np.ceil(self.steps_hist.percentile(50))) + 1
        b = -(-b // self.W) * self.W
        return int(min(max(b, self.W), cap))

    # -- admission ---------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        return int((self._rid_of >= 0).sum())

    def has_capacity(self) -> bool:
        return len(self._queue) < self.max_queue

    def submit(self, q, *, k: int = 10, rid: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               t_sched: Optional[float] = None) -> Optional[int]:
        """Enqueue one query. ``k`` results come back (k <= compiled
        EF). ``deadline_ms`` (or the scheduler's ``slo_ms``) arms
        deadline shedding; ``t_sched`` is the open-loop scheduled
        arrival the latency clock starts from (defaults to now).
        Returns the request id, or None when admission control SHEDS
        the request (queue full / deadline infeasible)."""
        if k > self.EF:
            raise ValueError(f"k={k} exceeds the compiled result "
                             f"width EF={self.EF}; construct the "
                             f"scheduler with ef>={k}")
        now = time.monotonic()
        t_sched = now if t_sched is None else t_sched
        dl_ms = deadline_ms if deadline_ms is not None else self.slo_ms
        deadline = None if dl_ms is None else t_sched + dl_ms / 1e3
        if deadline is not None and now > deadline:
            self._c_shed.labels(reason="deadline").inc()
            return None
        if len(self._queue) >= self.max_queue:
            self._c_shed.labels(reason="queue_full").inc()
            return None
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        ef_eff = int(min(max(k, self.ef_policy), self.EF))
        self._queue.append(_Pending(
            rid=rid, k=int(k), ef_eff=ef_eff, t_submit=now,
            t_sched=t_sched, deadline=deadline,
            q=np.asarray(q, np.float32).reshape(-1)))
        self._g_depth.set(len(self._queue))
        return rid

    # -- the execution loop ------------------------------------------------

    def _admit_step(self, dbv, span) -> int:
        """Admit whatever the queue holds into the lowest free slots
        and advance the bank — ONE fused compiled call when there are
        arrivals, a prefix step otherwise, both at the smallest ladder
        width covering the highest live slot."""
        with span.child("sched.admit") as adm:
            free = np.nonzero(self._rid_of < 0)[0]
            take: List[_Pending] = []
            if len(free) and self._queue:
                now = time.monotonic()
                while self._queue and len(take) < len(free):
                    p = self._queue.popleft()
                    if p.deadline is not None and now > p.deadline:
                        self._c_shed.labels(reason="deadline").inc()
                        adm.event("shed", rid=p.rid)
                        continue
                    take.append(p)
            if adm:
                # the wait from each request's scheduled arrival to
                # its slot
                waits = [now - p.t_sched for p in take] or [0.0]
                adm.set(admitted=len(take),
                        queued_ms=1e3 * sum(waits),
                        queued_max_ms=1e3 * max(waits))
            for row, p in enumerate(take):
                s = int(free[row])
                self._rid_of[s] = p.rid
                self._budget[s] = self._initial_budget(p.ef_eff)
                self._cap[s] = self._static_cap(p.ef_eff)
                self._keep[s] = p.ef_eff * self.rm
                self._meta[p.rid] = p
            occ = np.nonzero(self._rid_of >= 0)[0]
            if not len(occ):
                self._g_depth.set(len(self._queue))
                return 0
            wd = next(w for w in self.rungs if w >= int(occ[-1]) + 1)
            if take:
                q_new = np.zeros((wd, self._D), np.float32)
                slot_ids = np.full(wd, self.S, np.int32)
                ef_eff = np.full(wd, self.EFW, np.int32)
                budget = np.zeros(wd, np.int32)
                for row, p in enumerate(take):
                    s = int(free[row])
                    q_new[row] = p.q
                    slot_ids[row] = s
                    # deferred slots hold the WIDE filter-space pool,
                    # so the effective ef register scales with it
                    ef_eff[row] = p.ef_eff * self.wide
                    budget[row] = self._budget[s]
                    p.q = None
        span.set(width=wd, in_flight=len(occ))
        if take:
            self.state = self._admit_step_call(dbv, q_new, slot_ids,
                                               ef_eff, budget, wd, span)
            self._c_adm.inc(len(take))
        else:
            self.state = self._step_call(dbv, wd, span)
        self._g_depth.set(len(self._queue))
        return len(take)

    def _retire(self, span) -> List[Completion]:
        self._escalated = False
        occupied = self._rid_of >= 0
        if not occupied.any():
            return []
        with span.child("sched.wait"):
            done, ns, dhe = jax.device_get(
                (self.state.done, self.state.nsteps, self.state.dhe))
        live = self._live()
        if self.sharded:
            if live.any():
                done_eff = done[live].all(axis=0)
                ns_eff = ns[live].max(axis=0)
            else:
                done_eff = np.ones(self.S, bool)
                ns_eff = ns.max(axis=0)
            dhe_eff = dhe[live].sum(axis=0)
        else:
            done_eff, ns_eff, dhe_eff = done, ns, dhe
        finished = occupied & done_eff
        # budget escalation: an unconverged slot that spent its budget
        # doubles it (up to the static bound); at the bound it is
        # force-retired with exactly what the static program would
        # have returned
        stalled = occupied & ~done_eff & (ns_eff >= self._budget)
        forced = np.zeros(self.S, bool)
        if stalled.any():
            dirty = False
            for s in np.nonzero(stalled)[0]:
                if self._budget[s] < self._cap[s]:
                    self._budget[s] = min(2 * int(self._budget[s]),
                                          int(self._cap[s]))
                    self._c_esc.inc()
                    dirty = True
                else:
                    forced[s] = True
            if dirty:
                self._budget_stale = True
                self._escalated = True
        finished = finished | forced
        if not finished.any():
            return []
        if self.deferred:
            with span.child("sched.wait"):
                fi = np.asarray(self.state.F_i)
            # the deferred promote (cascade) + Dist.H passes, batched
            # over THIS tick's retiring slots at the full bank width
            # (non-retiring rows ride as fi = -1 pads — pure data, one
            # compiled shape): the exact final blocks of the
            # synchronous deferred program, so results are bit-equal
            with span.child("sched.rerank") as rr:
                db = self.svc.db
                fi_b = jnp.asarray(np.where(finished[:, None], fi, -1))
                if self.cascade:
                    keep = np.where(finished, self._keep,
                                    0).astype(np.int32)
                    _, fi_b = sj._retire_promote_jit(
                        db, self.state.qprep, fi_b, jnp.asarray(keep))
                rd, ri, rows = sj._retire_rerank_jit(
                    db, self.state.q_high, fi_b)
                with rr.child("sched.wait"):
                    fd, fi, rows = jax.device_get((rd, ri, rows))
                if rr:
                    rr.set(rows=int(rows.sum()))
            dhe_eff = dhe_eff + rows
        else:
            with span.child("sched.wait"):
                fd, fi = jax.device_get((self.state.F_d, self.state.F_i))
        with span.child("sched.complete") as cs:
            degraded = self.sharded and bool(~live.all())
            cov = self.svc._coverage(live) if degraded else 1.0
            now = time.monotonic()
            out: List[Completion] = []
            for s in np.nonzero(finished)[0]:
                p = self._meta.pop(int(self._rid_of[s]))
                kq = p.k
                if self.sharded:
                    ds = np.concatenate([fd[pp, s] for pp in
                                         np.nonzero(live)[0]])
                    gs = np.concatenate(
                        [np.where(fi[pp, s] >= 0,
                                  fi[pp, s] + self._offsets[pp], -1)
                         for pp in np.nonzero(live)[0]])
                    order = np.argsort(ds, kind="stable")[:kq]
                    ids, dists = gs[order], ds[order]
                else:
                    ids, dists = fi[s, :kq].copy(), fd[s, :kq].copy()
                lat = (now - p.t_sched) * 1e3
                out.append(Completion(
                    rid=p.rid, ids=ids, dists=dists, latency_ms=lat,
                    steps=int(ns_eff[s]), forced=bool(forced[s]),
                    degraded=degraded, coverage=cov,
                    dist_h=int(dhe_eff[s])))
                self.steps_hist.observe(float(ns_eff[s]))
                self.svc.stats.record_request(1, lat)
                if degraded:
                    self.svc.stats.record_degraded(cov)
                self._rid_of[s] = -1
                self._budget[s] = 0
            self._c_ret.inc(len(out))
            if cs:
                cs.set(retired=len(out),
                       dist_h_rows=sum(c.dist_h for c in out))
        return out

    def tick(self) -> List[Completion]:
        """One scheduler round: admit -> step -> escalate/retire.
        Returns the queries that completed this round (out-of-order by
        design — exactly-once per rid).

        Traced, the ``sched.tick`` root's children cover the round:
        ``sched.admit`` (queue, sheds, slot bookkeeping, staging),
        ``sched.launch`` (uploads, whose ``bytes`` it counts, and the
        compiled call's dispatch; an admitting launch holds
        ``sched.prepare``, the dispatch of the filter's device query
        prep over the uploaded rows),
        ``sched.wait`` (the host blocked on the device or its
        readback), ``sched.rerank`` (the deferred retirement passes)
        and ``sched.complete`` (the answers and their accounting); the
        escalation passes add launch / wait / complete again."""
        span = self.tracer.span("sched.tick")
        with span:
            dbv = self._db()
            self._admit_step(dbv, span)
            out = self._retire(span)
            # escalation pass: a budget-frozen slot whose budget just
            # doubled resumes NOW instead of waiting out a full
            # admission round — the extra prefix step is the same
            # compiled program (done slots stay masked), so the rare
            # deep query pays a partial re-step, not a whole tick
            passes = 0
            while self._escalated and passes < 2:
                occ = np.nonzero(self._rid_of >= 0)[0]
                if not len(occ):
                    break
                wd = next(w for w in self.rungs
                          if w >= int(occ[-1]) + 1)
                self.state = self._step_call(dbv, wd, span)
                out.extend(self._retire(span))
                passes += 1
            self._g_occ.set(self.in_flight / self.S)
            span.set(passes=passes)
        return out

    def drain(self) -> List[Completion]:
        """Tick until the queue and every slot are empty; returns all
        completions in retirement order."""
        out: List[Completion] = []
        while self._queue or (self._rid_of >= 0).any():
            out.extend(self.tick())
        return out

    @staticmethod
    def cache_sizes():
        """The slotted compiled-program cache sizes (step, admit,
        step_sharded, admit_sharded, step_prefix,
        step_prefix_sharded) — zero-recompile assertions."""
        return sj.slot_cache_sizes()
