"""Batched serving engine: continuous batching over KV-cache slots.

Requests enter a waiting queue, get prefilled into a free slot, and the
decode loop steps every active slot in one batched ``decode_step`` call
(one batch of GEMVs per projection — the PIM offload unit).  Finished
slots (EOS or max tokens) free immediately and the next waiting request
takes over — continuous batching, the production serving pattern.

The engine also carries the PIM telemetry: per decode step its
``OffloadController`` asks the OffloadPlanner which GEMV sites to run in
memory at the step's batch, and what the step would cost on a host-only
vs PIM-offloaded LPDDR5X system (the paper's motivating use case:
on-device LLM decode).

Program spans (``repro.obs``): ``serve.step`` per call, ``serve.queue``
per request from submit to admission, ``serve.admit`` per admission
(``serve.prefill``, ``serve.merge`` with the ``kv_bytes`` and
``state_bytes`` of the slot it merges, ``serve.first_token``), and per
decode ``serve.h2d``, ``serve.decode``, ``serve.sample``,
``serve.readback`` and ``serve.plan``.

Speculative decoding (``spec_decode=``, a
``scenarios.SpecDecodeConfig``): each serve tick runs one draft/verify
*round* per active slot instead of a single decode step.  The seeded
config decides how many draft tokens each request accepts this round
(keyed per ``(rid, round)``, so the schedule is independent of slot
order and identical to the model-free ``simulate_spec_decode`` mirror);
the engine realizes an advance of ``k + 1`` tokens as that many batched
decode sub-steps on the real target model — greedy speculative decoding
is output-identical to greedy vanilla decode, so the token streams stay
byte-equal to a vanilla run and the differential battery asserts it.
Slots whose round is shorter than the tick's longest ride along masked:
they feed their last token at an un-advanced position and their logits
are discarded; the garbage cache write at that position is overwritten
by their next genuine sub-step before anything reads it (the same
precedent as inactive slots decoding token 0).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import model as M
from .offload import OffloadPlanner
from .policy import OffloadController


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    eos: int = -1
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, slots: int = 4,
                 max_seq: int = 256,
                 planner: Optional[OffloadPlanner] = None,
                 controller: Optional[OffloadController] = None,
                 spec_decode=None):
        assert cfg.input_mode == "tokens", "engine serves token models"
        self.cfg, self.params = cfg, params
        self.slots = slots
        self.max_seq = max_seq
        self.cache = M.init_cache(cfg, slots, max_seq, jnp.float32)
        # bytes of one slot's K/V and of its recurrent (SSM, conv) state:
        # what an admission merges into the batched cache
        per_slot = {k: sum(x.nbytes for x in jax.tree.leaves(v)) // slots
                    for k, v in self.cache.items()}
        self._merge_bytes = dict(
            kv_bytes=per_slot.get("kv", 0) + per_slot.get("kv_scale", 0),
            state_bytes=per_slot.get("ssm", 0) + per_slot.get("conv", 0))
        self.active: list[Optional[Request]] = [None] * slots
        self.pos = np.zeros(slots, dtype=np.int32)
        self.waiting: list[Request] = []
        # Adaptive offload control: the controller sees every decode
        # step's live batch size and runs its policy (per-step
        # recompute, hysteresis, sticky — serving/policy.py); its
        # planner doubles as the telemetry planner unless one was
        # passed explicitly.
        self.controller = controller
        if planner is None and controller is not None:
            planner = controller.planner
        self.planner = planner
        self.stats = dict(steps=0, tokens=0, prefills=0)
        self.batch_occupancy: dict[int, int] = {}
        self.step_batches: list[int] = []      # trace: batch per step
        # Per-request scheduling record: driver tick of admission
        # (= prefill, in the monolithic engine) and of completion.  The
        # disaggregated cell pair (serving/cells.py) records the same
        # ticks, which is what the differential parity battery diffs.
        self.ticks = 0                         # step() calls, idle included
        self.admit_ticks: dict[int, int] = {}
        self.completions: dict[int, int] = {}
        # rid -> perf_counter_ns at submit, kept only while spans record
        self._queued: dict[int, int] = {}
        # Speculative decoding: the seeded accept/advance schedule
        # (duck-typed — scenarios.SpecDecodeConfig; None = vanilla) plus
        # per-request round counters and the per-tick advance telemetry
        # the mirror parity battery diffs.
        self.spec_decode = spec_decode
        self.spec_rounds: dict[int, int] = {}
        self.spec_drafted: dict[int, int] = {}
        self.spec_accepted: dict[int, int] = {}
        self.spec_advance: list[int] = []
        self.spec_substeps: list[int] = []

        self._decode = M.jit_decode_step(cfg)
        # Jitted like decode: one compile per prompt length, then ~ms
        # per prefill — eager prefill is the serving stack's tick-time
        # ceiling (a daemon admitting tens of requests per tick spends
        # its whole tick in op-by-op dispatch otherwise).
        self._prefill_fn = M.jit_prefill(cfg)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if obs.on():
            self._queued[req.rid] = time.perf_counter_ns()
        self.waiting.append(req)

    def _admit(self, tick: int):
        for slot in range(self.slots):
            if self.active[slot] is None and self.waiting:
                req = self.waiting.pop(0)
                t0 = self._queued.pop(req.rid, None)
                if t0 is not None:
                    obs.interval("serve.queue", t0, rid=req.rid)
                with obs.span("serve.admit", rid=req.rid, slot=slot,
                              prompt_len=len(req.prompt)):
                    self._prefill(slot, req)
                self.active[slot] = req
                self.admit_ticks[req.rid] = tick

    def _prefill(self, slot: int, req: Request):
        """Single-slot prefill into the batched cache (slot-masked)."""
        s = len(req.prompt)
        assert s < self.max_seq
        with obs.span("serve.prefill"):
            prompt = jnp.asarray(req.prompt, jnp.int32)[None]
            tmp_cache = M.init_cache(self.cfg, 1, self.max_seq, jnp.float32)
            logits, tmp_cache = self._prefill_fn(
                self.params, {"tokens": prompt}, tmp_cache)

        # merge the single-row cache into the batched cache at `slot`
        def merge(full, one):
            return full.at[:, slot:slot + 1].set(one)
        with obs.span("serve.merge", **self._merge_bytes):
            self.cache = jax.tree.map(merge, self.cache, tmp_cache)
        self.pos[slot] = s
        with obs.span("serve.first_token"):
            tok = int(jnp.argmax(logits[0]))
        req.out.append(tok)
        self.stats["prefills"] += 1

    # ------------------------------------------------------------------
    def step(self):
        """One batched decode step over all active slots."""
        with obs.span("serve.step", tick=self.ticks,
                      waiting=len(self.waiting)) as sp:
            batch = self._step()
            sp.set(batch=batch)
        return batch > 0

    def _step(self) -> int:
        tick = self.ticks
        self.ticks += 1          # idle ticks advance too (driver-aligned)
        self._admit(tick)
        act = [i for i, r in enumerate(self.active) if r is not None]
        if not act:
            return 0
        self.batch_occupancy[len(act)] = \
            self.batch_occupancy.get(len(act), 0) + 1
        if self.spec_decode is not None:
            self._spec_round(tick, act)
        else:
            next_tok = self._decode_rows(act)
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
            with obs.span("serve.plan") as sp:
                rec = self.controller.observe(len(act))
                sp.set(offloaded=rec.offloaded)
        self.stats["steps"] += 1
        return len(act)

    def _decode_rows(self, rows: list[int]) -> np.ndarray:
        """One batched ``decode_step`` that feeds each slot in ``rows``
        its last token (every other slot token 0); the next token of
        every slot, read back to the host."""
        with obs.span("serve.h2d"):
            tokens = np.zeros((self.slots, 1), dtype=np.int32)
            for i in rows:
                tokens[i, 0] = self.active[i].out[-1]
            # one position per slot (ragged decode positions)
            pos = jnp.asarray(self.pos, jnp.int32)
            tokens = jnp.asarray(tokens)
        with obs.span("serve.decode"):
            logits, self.cache = self._decode(self.params, self.cache,
                                              tokens, pos)
        # one argmax over the whole batch on device, one host transfer —
        # not a device->host sync per active slot
        with obs.span("serve.sample"):
            next_tok = jnp.argmax(logits, axis=-1)
        with obs.span("serve.readback"):
            return np.asarray(next_tok).reshape(-1)

    def _spec_round(self, tick: int, act: list[int]) -> None:
        """One speculative round per active slot, as batched sub-steps.

        The seeded schedule fixes each slot's advance up front; the
        tick then runs ``max(advance)`` batched decode sub-steps, each
        slot participating genuinely for its own first ``advance`` of
        them and riding along masked afterwards.  Each genuine sub-step
        is bit-identical to a vanilla decode step for that slot (the
        model is per-slot independent), so token streams match vanilla.
        """
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
            next_tok = self._decode_rows(
                [i for i in act if self.active[i] is not None])
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

    def spec_report(self) -> dict:
        """Aggregate speculative telemetry (all zeros when vanilla or
        nothing ran — the neutral-summary contract)."""
        drafted = sum(self.spec_drafted.values())
        accepted = sum(self.spec_accepted.values())
        return dict(rounds=sum(self.spec_rounds.values()),
                    drafted=drafted, accepted=accepted,
                    wasted=drafted - accepted,
                    substeps=sum(self.spec_substeps),
                    per_tick_advance=list(self.spec_advance))

    def run(self, max_steps: int = 1000) -> dict:
        while (any(self.active) or self.waiting) and max_steps > 0:
            self.step()
            max_steps -= 1
        return self.summary()

    def summary(self) -> dict:
        """Run stats + PIM telemetry (+ policy report when controlled).

        Split out of :meth:`run` so trace-driven drivers — scenario
        loops that interleave arrivals with steps — get the identical
        record without going through ``run``'s step loop.
        """
        out = dict(self.stats)
        out["batch_occupancy"] = dict(self.batch_occupancy)
        # Derived metrics stay neutral on zero-request runs (a --quick
        # drain-refill with a tiny step budget completes nothing): no
        # raises, no 0/0 — completed 0, in-flight counts, rate 0.0.
        out["completed"] = len(self.completions)
        out["in_flight"] = (sum(r is not None for r in self.active)
                            + len(self.waiting))
        out["tokens_per_step"] = (self.stats["tokens"] / self.stats["steps"]
                                  if self.stats["steps"] else 0.0)
        if self.planner is not None:
            # One batched fleet query builds the site plan; per-batch-size
            # speedups are then pure arithmetic over the cached decisions.
            tel = self.planner.decode_speedup(batch=max(1, self.slots))
            batches = sorted(self.batch_occupancy) or [max(1, self.slots)]
            tel["per_batch_speedup"] = {
                b: self.planner.decode_speedup(batch=b)["speedup"]
                for b in batches}
            if self.batch_occupancy:
                # occupancy-weighted offload: crossover per step, not per
                # run — the batch-occupancy histogram weights each decode
                # step's offload decision by its true batch size.
                tel["occupancy_weighted"] = \
                    self.planner.occupancy_weighted_speedup(
                        self.batch_occupancy)
            out["pim_telemetry"] = tel
        if self.controller is not None:
            out["policy"] = self.controller.report()
        return out
