"""Disaggregated serving: a prefill cell and a decode cell joined by a
KV-handoff queue.

Production LLM serving splits prefill (compute-bound, long-prompt) and
decode (memory-bound, the LP5X-PIM sweet spot) into cells with
different batching and offload economics.  This module is that split
for :class:`~repro.serving.engine.ServingEngine`:

* :class:`PrefillCell` owns the admission queue (per-tenant SLO
  classes, FIFO within a class, aging so throughput tenants cannot
  starve under latency bursts) and performs prompt prefills — each
  produces a single-row KV cache plus the first token — up to a
  per-tick budget, pushing results onto the handoff queue.
* :class:`KVHandoffQueue` is the bounded FIFO between the cells; the
  prefill cell stalls rather than overrun it, and its peak depth is
  part of every report (the fuzzed bound property).
* :class:`DecodeCell` owns the batched KV cache and slots: handed-off
  requests merge into free slots the moment slots free (continuous
  batching — slot reclamation on completion, never batch-synchronous
  refill), and every tick runs ONE batched ``decode_step`` over all
  active slots, exactly the monolithic engine's decode loop.

Each cell can carry its own :class:`OffloadController` policy AND its
own :class:`~repro.core.engine.BackendScope` (lane backend, mesh,
device cap, circuit breaker): a cell activates its scope around its
tick work, so a prefill-side backend fault or breaker trip never
changes the decode cell's ladder — the cells' execution resources are
provisioned independently, like real disaggregated deployments.
Without scopes both cells run under the process-default scope (the
classic ``configure_lane_backend`` / ``configure_lane_mesh`` state).
Both cells still share the process-global resolved-lane LRU and
warm-start caches (``core/engine.py`` / ``core/warmstart.py``), so a
prefill→decode handoff never re-resolves lanes (asserted in
``tests/test_disagg.py``).

Under ``DisaggConfig.mirror()`` (unbounded prefill/handoff, one SLO
class) the pair replays the monolithic engine tick-exactly: identical
per-request completion ticks, batch occupancy, tokens and controller
telemetry — the differential contract ``tests/test_disagg.py`` pins
against the golden bursty trace.  The scheduling semantics themselves
are specified once in ``serving/scenarios.py`` (``simulate_disagg`` /
``_admission_pick``); this module is the independent real-model
implementation the parity battery diffs against it.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import faults
from repro.core import engine as lane_engine
from repro.models import model as M
from .engine import Request
from .offload import OffloadPlanner
from .policy import OffloadController
from .scenarios import (DisaggConfig, SLO_CLASSES, SLO_LATENCY,
                        SLO_THROUGHPUT)


def _scope_ctx(scope):
    """A cell's scope activation: ``backend_scope`` when the cell
    carries one, a no-op otherwise (so unscoped cells keep inheriting
    whatever scope — default or enclosing — is already active)."""
    return (lane_engine.backend_scope(scope) if scope is not None
            else contextlib.nullcontext())


class AdmissionQueue:
    """Per-SLO-class FIFO admission with aging (the anti-starvation rule).

    The pick order — starved throughput requests (waited >=
    ``starvation_age`` ticks) oldest-first, then latency FIFO, then
    throughput FIFO — implements the same spec as
    ``scenarios._admission_pick``; the property suite fuzzes both and
    the cell-vs-simulator parity test holds them together.  With a
    single class every rule degenerates to plain FIFO.
    """

    def __init__(self, starvation_age: int = 8):
        self.starvation_age = int(starvation_age)
        self._entries: list[tuple] = []    # (enq_tick, seq, Request, slo)
        self._seq = 0

    def __len__(self) -> int:
        return len(self._entries)

    def push(self, req: Request, slo: str, tick: int) -> None:
        if slo not in SLO_CLASSES:
            raise ValueError(f"unknown SLO class {slo!r}; "
                             f"choose from {SLO_CLASSES}")
        self._entries.append((tick, self._seq, req, slo))
        self._seq += 1

    def pop(self, tick: int) -> tuple[Request, str, int]:
        """(request, slo, enqueue tick) of the next admission."""
        starved = [i for i, (enq, _, _, slo) in enumerate(self._entries)
                   if slo == SLO_THROUGHPUT
                   and tick - enq >= self.starvation_age]
        if starved:
            pick = min(starved, key=lambda i: self._entries[i][:2])
        else:
            latency = [i for i, e in enumerate(self._entries)
                       if e[3] == SLO_LATENCY]
            pool = latency or range(len(self._entries))
            pick = min(pool, key=lambda i: self._entries[i][:2])
        enq, _, req, slo = self._entries.pop(pick)
        return req, slo, enq

    def shed(self, tick: int) -> tuple[Request, str, int]:
        """(request, slo, enqueue tick) of the entry to drop under
        admission pressure — the exact inverse of :meth:`pop`, same
        spec as ``scenarios._shed_pick``: youngest non-starved
        throughput request first, then youngest latency, starved
        throughput only when nothing else waits (aging preserved)."""
        fresh = [i for i, (enq, _, _, slo) in enumerate(self._entries)
                 if slo == SLO_THROUGHPUT
                 and tick - enq < self.starvation_age]
        if fresh:
            pick = max(fresh, key=lambda i: self._entries[i][:2])
        else:
            latency = [i for i, e in enumerate(self._entries)
                       if e[3] == SLO_LATENCY]
            pool = latency or range(len(self._entries))
            pick = max(pool, key=lambda i: self._entries[i][:2])
        enq, _, req, slo = self._entries.pop(pick)
        return req, slo, enq

    def wait_entries(self) -> list[tuple[int, str]]:
        """(enqueue tick, slo) of every waiting request — the per-class
        wait-age telemetry the autoscaler's grow signal reads."""
        return [(enq, slo) for enq, _, _, slo in self._entries]


@dataclasses.dataclass
class KVHandoff:
    """One prefilled request in flight between the cells: the request,
    its single-row KV cache, its sequence position after prefill."""

    req: Request
    cache: object            # 1-row cache pytree from M.prefill
    pos: int
    slo: str
    prefill_tick: int


class KVHandoffQueue:
    """Bounded FIFO of prefilled requests awaiting a decode slot."""

    def __init__(self, bound: int | None = None):
        self.bound = bound
        self._q: list[KVHandoff] = []
        self.handoffs = 0
        self.max_depth = 0
        self.waits: list[int] = []   # per-pop ticks spent in the queue

    def __len__(self) -> int:
        return len(self._q)

    def room(self) -> bool:
        inj = faults.injector()
        if inj is not None and inj.should_fail("handoff") is not None:
            # Simulated handoff pressure: report the queue full so the
            # prefill cell stalls this tick — the graceful path the
            # bound already exercises, never the overrun crash below.
            faults.record_event("handoff", "inject",
                                "simulated handoff pressure")
            faults.record_event("handoff", "stall",
                                "prefill cell stalls (queue reported full)")
            return False
        return self.bound is None or len(self._q) < self.bound

    def push(self, item: KVHandoff) -> None:
        if not self.room():
            raise RuntimeError(f"KV-handoff queue overrun (bound "
                               f"{self.bound}) — prefill cell must stall")
        self._q.append(item)
        self.handoffs += 1
        self.max_depth = max(self.max_depth, len(self._q))

    def pop(self, tick: int | None = None) -> KVHandoff:
        """FIFO pop; with ``tick`` the item's queue wait (ticks between
        prefill and decode admission) is recorded for telemetry."""
        item = self._q.pop(0)
        if tick is not None:
            self.waits.append(int(tick) - item.prefill_tick)
        return item

    def report(self) -> dict:
        return dict(bound=self.bound, depth=len(self._q),
                    handoffs=self.handoffs, max_depth=self.max_depth)

    def wait_report(self) -> dict:
        """Queue-wait telemetry, guarded for empty populations: a
        zero-request (or all-shed) run reports neutral ``0.0`` means —
        the PR 7 zero-request convention — never a divide by zero.
        Kept out of :meth:`report` so the golden disagg traces stay
        byte-identical."""
        n = len(self.waits)
        return dict(pops=n,
                    mean_wait=(sum(self.waits) / n if n else 0.0),
                    max_wait=(max(self.waits) if n else 0))


class PrefillCell:
    """Admission + prompt prefill; produces KV handoffs.

    The prefill computation is byte-identical to the monolithic
    engine's ``_prefill`` (same 1-row cache init, same ``M.prefill``
    call, same greedy first token); only the merge into the batched
    cache is deferred to the decode cell — which is what lets this cell
    run ahead of slot availability.
    """

    def __init__(self, cfg: ArchConfig, params, max_seq: int,
                 budget: int | None = None, starvation_age: int = 8,
                 admission_capacity: int | None = None,
                 controller: Optional[OffloadController] = None,
                 scope: "lane_engine.BackendScope | None" = None):
        self.cfg, self.params = cfg, params
        self.max_seq = max_seq
        self.budget = budget
        self.admission_capacity = admission_capacity
        self.queue = AdmissionQueue(starvation_age)
        self.controller = controller
        self.scope = scope
        self.stats = dict(prefills=0, ticks=0)
        self.prefill_ticks: dict[int, int] = {}
        self.enq_ticks: dict[int, int] = {}
        self.slo_of: dict[int, str] = {}
        self.shed: dict[int, int] = {}    # rid -> shed tick
        # Jitted like the decode cell's step: one compile per prompt
        # length keeps a budget-6 prefill tick in the milliseconds.
        self._prefill_fn = M.jit_prefill(cfg)

    def submit(self, req: Request, slo: str, tick: int) -> None:
        self.queue.push(req, slo, tick)
        self.enq_ticks[req.rid] = tick
        self.slo_of[req.rid] = slo
        while (self.admission_capacity is not None
               and len(self.queue) > self.admission_capacity):
            # SLO-aware load shedding: drop the lowest-priority waiter
            # (AdmissionQueue.shed = inverse admission order) instead of
            # letting pressure reach the handoff-overrun invariant.
            victim, vslo, _ = self.queue.shed(tick)
            self.shed[victim.rid] = tick
            faults.record_event(
                "admission", "shed",
                f"rid={victim.rid} slo={vslo} "
                f"(capacity {self.admission_capacity})", tick=tick)

    def _prefill(self, req: Request) -> KVHandoff:
        s = len(req.prompt)
        assert s < self.max_seq
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        cache = M.init_cache(self.cfg, 1, self.max_seq, jnp.float32)
        logits, cache = self._prefill_fn(self.params,
                                         {"tokens": prompt}, cache)
        req.out.append(int(jnp.argmax(logits[0])))
        self.stats["prefills"] += 1
        return KVHandoff(req=req, cache=cache, pos=s, slo="", prefill_tick=0)

    def tick(self, t: int, handoff: KVHandoffQueue) -> int:
        """Prefill up to ``budget`` admitted requests while the handoff
        queue has room; returns the number prefilled this tick.  All
        lane work (controller replans, planner touches) runs under this
        cell's backend scope when one is set."""
        with _scope_ctx(self.scope):
            return self._tick(t, handoff)

    def _tick(self, t: int, handoff: KVHandoffQueue) -> int:
        self.stats["ticks"] += 1
        n = 0
        while ((self.budget is None or n < self.budget)
               and handoff.room() and len(self.queue)):
            req, slo, _ = self.queue.pop(t)
            item = self._prefill(req)
            item.slo, item.prefill_tick = slo, t
            self.prefill_ticks[req.rid] = t
            handoff.push(item)
            n += 1
        if self.controller is not None and n > 0:
            self.controller.observe(n)
        return n

    def report(self) -> dict:
        out = dict(self.stats)
        out["waiting"] = len(self.queue)
        if self.admission_capacity is not None:
            out["shed"] = len(self.shed)
        if self.controller is not None:
            out["policy"] = self.controller.report()
        return out


class DecodeCell:
    """Batched continuous-batching decode over KV-cache slots.

    The decode loop is the monolithic engine's, verbatim in semantics:
    one batched ``decode_step`` per tick over every active slot, one
    device argmax, slots freed the instant their request completes.
    Admission happens from the handoff queue instead of a waiting list
    — handed-off single-row caches merge into the batched cache at the
    lowest free slot, FIFO.
    """

    def __init__(self, cfg: ArchConfig, params, slots: int, max_seq: int,
                 planner: Optional[OffloadPlanner] = None,
                 controller: Optional[OffloadController] = None,
                 spec_decode=None,
                 scope: "lane_engine.BackendScope | None" = None):
        assert cfg.input_mode == "tokens", "cells serve token models"
        self.cfg, self.params = cfg, params
        self.slots = slots
        # Admission limit for autoscaling: the cache stays allocated at
        # ``slots`` (so growing is free) and only slots below ``limit``
        # accept new work; after a shrink, busy slots above the limit
        # finish their requests but are never refilled (lame-duck).
        self.limit = slots
        self.scope = scope
        self.max_seq = max_seq
        self.cache = M.init_cache(cfg, slots, max_seq, jnp.float32)
        self.active: list[Optional[Request]] = [None] * slots
        self.pos = np.zeros(slots, dtype=np.int32)
        self.controller = controller
        if planner is None and controller is not None:
            planner = controller.planner
        self.planner = planner
        self.stats = dict(steps=0, tokens=0)
        self.batch_occupancy: dict[int, int] = {}
        self.step_batches: list[int] = []
        self.admit_ticks: dict[int, int] = {}
        self.completions: dict[int, int] = {}
        # Speculative decoding: same seeded accept/advance schedule as
        # the monolithic engine (scenarios.SpecDecodeConfig or None).
        self.spec_decode = spec_decode
        self.spec_rounds: dict[int, int] = {}
        self.spec_drafted: dict[int, int] = {}
        self.spec_accepted: dict[int, int] = {}
        self.spec_advance: list[int] = []
        self.spec_substeps: list[int] = []
        self._decode = M.jit_decode_step(cfg)

    def free_slots(self) -> int:
        return sum(1 for r in self.active if r is None)

    def admit(self, handoff: KVHandoffQueue, tick: int) -> int:
        """Merge handed-off requests into free slots below the admission
        limit, FIFO, lowest slot first — zero lane work: the merge is a
        pure cache write."""
        n = 0
        for slot in range(min(self.slots, self.limit)):
            if self.active[slot] is None and len(handoff):
                item = handoff.pop(tick)

                def merge(full, one):
                    return full.at[:, slot:slot + 1].set(one)
                self.cache = jax.tree.map(merge, self.cache, item.cache)
                self.pos[slot] = item.pos
                self.active[slot] = item.req
                self.admit_ticks[item.req.rid] = tick
                n += 1
        return n

    def step(self, tick: int) -> int:
        """One batched decode step; returns the batch size (0 = idle).
        Runs under this cell's backend scope when one is set."""
        with _scope_ctx(self.scope):
            return self._step(tick)

    def _step(self, tick: int) -> int:
        act = [i for i, r in enumerate(self.active) if r is not None]
        if not act:
            return 0
        self.batch_occupancy[len(act)] = \
            self.batch_occupancy.get(len(act), 0) + 1
        if self.spec_decode is not None:
            self._spec_round(tick, act)
        else:
            tokens = np.zeros((self.slots, 1), dtype=np.int32)
            for i in act:
                tokens[i, 0] = self.active[i].out[-1]
            pos = jnp.asarray(self.pos, jnp.int32)
            logits, self.cache = self._decode(self.params, self.cache,
                                              jnp.asarray(tokens), pos)
            next_tok = np.asarray(jnp.argmax(logits, axis=-1)).reshape(-1)
            for i in act:
                req = self.active[i]
                tok = int(next_tok[i])
                req.out.append(tok)
                self.pos[i] += 1
                self.stats["tokens"] += 1
                if (tok == req.eos or len(req.out) >= req.max_new
                        or self.pos[i] >= self.max_seq - 1):
                    req.done = True
                    self.active[i] = None
                    self.completions[req.rid] = tick
        self.step_batches.append(len(act))
        if self.controller is not None:
            self.controller.observe(len(act))
        self.stats["steps"] += 1
        return len(act)

    def _spec_round(self, tick: int, act: list[int]) -> None:
        """One speculative round per active slot — semantics identical
        to ``ServingEngine._spec_round`` (the differential battery
        holds the two implementations and the model-free mirror
        together)."""
        sd = self.spec_decode
        adv: dict[int, int] = {}
        for i in act:
            req = self.active[i]
            rem = max(1, req.max_new - len(req.out))
            a, drf, acc = sd.advance(req.rid,
                                     self.spec_rounds.get(req.rid, 0),
                                     rem)
            self.spec_rounds[req.rid] = \
                self.spec_rounds.get(req.rid, 0) + 1
            self.spec_drafted[req.rid] = \
                self.spec_drafted.get(req.rid, 0) + drf
            self.spec_accepted[req.rid] = \
                self.spec_accepted.get(req.rid, 0) + acc
            adv[i] = a
        nsub = max(adv.values())
        advanced = 0
        for s in range(nsub):
            live = [i for i in act
                    if s < adv[i] and self.active[i] is not None]
            if not live:
                break
            tokens = np.zeros((self.slots, 1), dtype=np.int32)
            for i in act:
                if self.active[i] is not None:
                    tokens[i, 0] = self.active[i].out[-1]
            pos = jnp.asarray(self.pos, jnp.int32)
            logits, self.cache = self._decode(self.params, self.cache,
                                              jnp.asarray(tokens), pos)
            next_tok = np.asarray(jnp.argmax(logits, axis=-1)).reshape(-1)
            for i in live:
                req = self.active[i]
                tok = int(next_tok[i])
                req.out.append(tok)
                self.pos[i] += 1
                self.stats["tokens"] += 1
                advanced += 1
                if (tok == req.eos or len(req.out) >= req.max_new
                        or self.pos[i] >= self.max_seq - 1):
                    req.done = True
                    self.active[i] = None
                    self.completions[req.rid] = tick
        self.spec_advance.append(advanced)
        self.spec_substeps.append(nsub)


class DisaggServingEngine:
    """The composed cell pair: one ``step()`` call is one driver tick.

    Drop-in for :class:`ServingEngine` in the scenario driver — same
    ``submit`` / ``step`` / ``run`` / ``summary`` surface plus
    ``waiting`` / ``active`` / ``step_batches`` views — with the
    disaggregated internals: per-tick the prefill cell admits and
    prefills (SLO-aware, budgeted, handoff-bounded), the decode cell
    reclaims freed slots from the handoff queue and runs one batched
    decode step.
    """

    def __init__(self, cfg: ArchConfig, params, slots: int = 4,
                 max_seq: int = 256,
                 disagg: DisaggConfig | None = None,
                 planner: Optional[OffloadPlanner] = None,
                 controller: Optional[OffloadController] = None,
                 prefill_controller: Optional[OffloadController] = None,
                 spec_decode=None,
                 prefill_scope: "lane_engine.BackendScope | None" = None,
                 decode_scope: "lane_engine.BackendScope | None" = None):
        self.disagg = disagg or DisaggConfig.mirror()
        self.handoff = KVHandoffQueue(self.disagg.handoff_bound)
        self.prefill_cell = PrefillCell(
            cfg, params, max_seq, budget=self.disagg.prefill_budget,
            starvation_age=self.disagg.starvation_age,
            admission_capacity=self.disagg.admission_capacity,
            controller=prefill_controller, scope=prefill_scope)
        self.decode_cell = DecodeCell(cfg, params, slots, max_seq,
                                      planner=planner,
                                      controller=controller,
                                      spec_decode=spec_decode,
                                      scope=decode_scope)
        self.ticks = 0

    # -- ServingEngine-compatible views --------------------------------
    @property
    def active(self) -> list:
        return self.decode_cell.active

    @property
    def waiting(self) -> int:
        """Truthy while any request sits before its decode slot."""
        return len(self.prefill_cell.queue) + len(self.handoff)

    @property
    def step_batches(self) -> list[int]:
        return self.decode_cell.step_batches

    @property
    def completions(self) -> dict[int, int]:
        return self.decode_cell.completions

    @property
    def shed(self) -> dict[int, int]:
        """rid -> tick of every request dropped by admission shedding."""
        return self.prefill_cell.shed

    @property
    def planner(self):
        return self.decode_cell.planner

    @property
    def controller(self):
        return self.decode_cell.controller

    def submit(self, req: Request, slo: str = SLO_LATENCY) -> None:
        self.prefill_cell.submit(req, slo, self.ticks)

    def spec_report(self) -> dict:
        """Aggregate speculative telemetry — the decode cell's, in the
        monolithic engine's ``spec_report`` shape."""
        dec = self.decode_cell
        drafted = sum(dec.spec_drafted.values())
        accepted = sum(dec.spec_accepted.values())
        return dict(rounds=sum(dec.spec_rounds.values()),
                    drafted=drafted, accepted=accepted,
                    wasted=drafted - accepted,
                    substeps=sum(dec.spec_substeps),
                    per_tick_advance=list(dec.spec_advance))

    def step(self) -> bool:
        """One tick: prefill → handoff admission → batched decode.
        Returns True when the decode cell actually stepped."""
        t = self.ticks
        self.ticks += 1
        self.prefill_cell.tick(t, self.handoff)
        self.decode_cell.admit(self.handoff, t)
        return self.decode_cell.step(t) > 0

    def run(self, max_steps: int = 1000) -> dict:
        while (any(self.active) or self.waiting) and max_steps > 0:
            self.step()
            max_steps -= 1
        return self.summary()

    # -- reporting -----------------------------------------------------
    def request_ticks(self) -> dict:
        """Per-request scheduling record, keyed like the model-free
        simulator's output so the parity suite can diff them raw."""
        return dict(prefill_ticks=dict(self.prefill_cell.prefill_ticks),
                    admit_ticks=dict(self.decode_cell.admit_ticks),
                    completion_ticks=dict(self.decode_cell.completions))

    def wait_telemetry(self, tick: int | None = None) -> dict:
        """Per-class admission-wait ages of the requests still waiting
        — the live SLO pressure signal the autoscaler's grow rule reads
        each tick.  Neutral over empty queues (``max_wait=0``,
        ``mean_wait=0.0``), matching the zero-request convention."""
        t = self.ticks if tick is None else int(tick)
        ages: dict[str, list[int]] = {cls: [] for cls in SLO_CLASSES}
        for enq, slo in self.prefill_cell.queue.wait_entries():
            ages[slo].append(t - enq)
        out = {}
        for cls in SLO_CLASSES:
            a = ages[cls]
            out[cls] = dict(waiting=len(a),
                            max_wait=(max(a) if a else 0),
                            mean_wait=(sum(a) / len(a) if a else 0.0))
        return out

    def scopes_report(self) -> dict | None:
        """Per-cell backend-scope record (None when neither cell is
        scoped, so unscoped summaries/traces stay byte-identical)."""
        pre, dec = self.prefill_cell.scope, self.decode_cell.scope
        if pre is None and dec is None:
            return None
        return dict(
            prefill=(pre.describe() if pre is not None else None),
            decode=(dec.describe() if dec is not None else None))

    def _slo_summary(self) -> dict:
        """Per-class wait/latency means — neutral (0.0) over zero
        completions, never a divide by zero."""
        out = {}
        cell = self.prefill_cell
        for cls in SLO_CLASSES:
            rids = [r for r, s in cell.slo_of.items() if s == cls]
            done = [r for r in rids if r in self.completions]
            waits = [self.decode_cell.admit_ticks[r] - cell.enq_ticks[r]
                     for r in done]
            lats = [self.completions[r] - cell.enq_ticks[r] for r in done]
            out[cls] = dict(
                submitted=len(rids), completed=len(done),
                mean_admit_wait=(sum(waits) / len(done) if done else 0.0),
                mean_completion_ticks=(sum(lats) / len(done)
                                       if done else 0.0))
        return out

    def summary(self) -> dict:
        """The monolithic engine's summary shape (steps, tokens,
        prefills, occupancy, PIM telemetry, policy report) plus the
        disaggregation record under ``"disagg"``.  Every derived metric
        is neutral on zero-request runs."""
        dec = self.decode_cell
        steps = dec.stats["steps"]
        out = dict(steps=steps, tokens=dec.stats["tokens"],
                   prefills=self.prefill_cell.stats["prefills"])
        out["batch_occupancy"] = dict(dec.batch_occupancy)
        out["completed"] = len(self.completions)
        out["in_flight"] = (sum(r is not None for r in dec.active)
                            + self.waiting)
        out["tokens_per_step"] = (dec.stats["tokens"] / steps
                                  if steps else 0.0)
        if dec.planner is not None:
            tel = dec.planner.decode_speedup(batch=max(1, dec.slots))
            batches = sorted(dec.batch_occupancy) or [max(1, dec.slots)]
            tel["per_batch_speedup"] = {
                b: dec.planner.decode_speedup(batch=b)["speedup"]
                for b in batches}
            if dec.batch_occupancy:
                tel["occupancy_weighted"] = \
                    dec.planner.occupancy_weighted_speedup(
                        dec.batch_occupancy)
            out["pim_telemetry"] = tel
        if dec.controller is not None:
            out["policy"] = dec.controller.report()
        out["disagg"] = dict(
            config=self.disagg.to_record(),
            handoff=self.handoff.report(),
            prefill=self.prefill_cell.report(),
            slo={str(r): s for r, s in
                 sorted(self.prefill_cell.slo_of.items())},
            per_class=self._slo_summary(),
            requests={k: {str(r): t for r, t in sorted(v.items())}
                      for k, v in self.request_ticks().items()})
        if self.disagg.admission_capacity is not None:
            # Key present only under bounded admission so pre-shedding
            # golden traces stay byte-identical.
            out["disagg"]["shed"] = {
                str(r): t for r, t in sorted(self.shed.items())}
        scopes = self.scopes_report()
        if scopes is not None:
            # Same convention: only scoped cell pairs grow the key.
            out["disagg"]["scopes"] = scopes
        return out
