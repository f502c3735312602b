"""Serving cells: the program's ``ServingEngine`` under an open-loop or an
offline load, timed on the host clock, then checked against the plain
reference.

Set-up builds the engine once (weights from the seed on the device, the
KV cache, the offload planner of the whole model with its lanes from the
snapshot under ``.jax_cache/``), then warms every prompt length the mix
can send, and every slot, through ``step()`` itself.  The window drives
that same engine.  Each ``step()`` call is one host span
(``bench.step``, numbered); after the call it is tagged ``admit`` if the
engine prefilled during it and ``decode`` otherwise.
"""
from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

import loadgen
from model_config import arch_config, dims
from reference import model_ref

LANES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache", "bench-lanes")


class Tracked:
    """One request as the client sees it."""

    __slots__ = ("req", "due", "times", "seen", "prompt_len", "slot")

    def __init__(self, req, due: float):
        self.req, self.due = req, due
        self.times: list[float] = []
        self.seen = 0
        self.prompt_len = len(req.prompt)
        self.slot = None


def build(c: dict, seed: int):
    """The engine of config file ``c`` with weights from ``seed``."""
    import jax

    from repro.core import warmstart
    from repro.serving.engine import ServingEngine
    from repro.serving.offload import OffloadPlanner
    from repro.serving.policy import OffloadController
    from weights import make_params

    sv = c["serve"]
    params = make_params(c, loadgen.jax_seed(seed))
    jax.block_until_ready(params)
    lanes = os.path.join(LANES_DIR, c["name"])
    os.makedirs(lanes, exist_ok=True)
    loaded = warmstart.load_lane_snapshot(lanes)
    planner = OffloadPlanner(arch_config(c, n_layers=sv["planner_layers"]))
    ctrl = OffloadController(planner, policy="per-step")
    eng = ServingEngine(arch_config(c), params, slots=sv["slots"],
                        max_seq=sv["max_seq"], controller=ctrl)
    return eng, params, (lanes, loaded)


class Driver:
    """Drives one engine and keeps the client's and the steps' records."""

    def __init__(self, eng):
        import jax

        self.eng = eng
        self.ann = jax.profiler.TraceAnnotation
        self.live: list[Tracked] = []
        self.steps: list[dict] = []
        self.rid = 0

    def submit(self, prompt: np.ndarray, max_new: int, due: float):
        from repro.serving.engine import Request

        req = Request(rid=self.rid, prompt=prompt, max_new=max_new)
        self.rid += 1
        t = Tracked(req, due)
        self.eng.submit(req)
        self.live.append(t)
        return t

    def step(self) -> dict:
        eng = self.eng
        pre, nb = eng.stats["prefills"], len(eng.step_batches)
        i = len(self.steps)
        with self.ann("bench.step", i=i):
            t0 = time.perf_counter()
            eng.step()
            t1 = time.perf_counter()
        rec = dict(i=i, t0=t0, t1=t1, admit=eng.stats["prefills"] > pre,
                   batch=eng.step_batches[-1]
                   if len(eng.step_batches) > nb else 0,
                   tokens=0, prefill=[], decode_ctx=[])
        slot_of = {id(r): i for i, r in enumerate(eng.active)
                   if r is not None}
        keep = []
        for t in self.live:
            if t.slot is None:
                t.slot = slot_of.get(id(t.req))
            out = t.req.out
            for j in range(t.seen, len(out)):
                t.times.append(t1)
                rec["tokens"] += 1
                if j == 0:
                    rec["prefill"].append(t.prompt_len)
                else:
                    rec["decode_ctx"].append(t.prompt_len + j)
            t.seen = len(out)
            if not t.req.done:
                keep.append(t)
        self.live = keep
        self.steps.append(rec)
        return rec

    def busy(self) -> bool:
        return bool(self.live)


def warm(drv: Driver, lengths: list[int], seed: int, vocab: int) -> None:
    """Every prompt length the run will send, through ``step()``, enough
    of them to fill every slot more than once."""
    g = loadgen.rng(seed, 99)
    slots = drv.eng.slots
    while len(lengths) < 2 * slots:
        lengths = lengths + lengths
    for n in lengths:
        drv.submit(g.integers(0, vocab, n).astype(np.int32), 2, 0.0)
    while drv.busy():
        drv.step()
    drv.steps.clear()


def open_loop(drv: Driver, mix: dict, seed: int, seconds: float,
              vocab: int, rate=None):
    """Send the window's requests on their schedule; drain up to
    ``drain_s`` after the window for their first and last tokens."""
    reqs = loadgen.open_loop(mix, seed, seconds, vocab, rate=rate)
    tracked, late = [], []
    t0 = time.perf_counter()
    nxt = 0
    stop = t0 + seconds + mix["drain_s"]
    backlog = None
    while True:
        now = time.perf_counter()
        if backlog is None and now >= t0 + seconds:
            backlog = len(drv.eng.waiting)
        while nxt < len(reqs) and t0 + reqs[nxt]["arrival_s"] <= now:
            r = reqs[nxt]
            due = t0 + r["arrival_s"]
            tracked.append(drv.submit(r["prompt"], r["max_new"], due))
            late.append(now - due)
            nxt += 1
        if now >= stop:
            break
        if drv.busy():
            drv.step()
        elif nxt >= len(reqs):
            break
        else:
            time.sleep(max(0.0, t0 + reqs[nxt]["arrival_s"] - now))
    print(f"offered {len(reqs) / seconds:.3f} req/s, "
          f"{sum(r['max_new'] for r in reqs) / seconds:.1f} tokens/s; "
          f"{backlog} waiting at the window's end", file=sys.stderr)
    return tracked, t0, t0 + seconds, late, stop


def offline(drv: Driver, mix: dict, seed: int, seconds: float,
            vocab: int):
    """Keep ``backlog`` requests waiting for the whole window."""
    pool = loadgen.offline(mix, seed, vocab, blocks=mix["blocks"])
    tracked = []
    t0 = time.perf_counter()
    end = t0
    nxt = 0
    while end - t0 < seconds:
        while len(drv.eng.waiting) < mix["backlog"]:
            r = pool[nxt % len(pool)]
            nxt += 1
            tracked.append(drv.submit(r["prompt"], r["max_new"], t0))
        end = drv.step()["t1"]
    return tracked, t0, end


def _pct(x, q) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def latency_metrics(tracked, t0: float, t_end: float, drain_end: float,
                    steps: list[dict], open_loop_kind: bool) -> dict:
    """End-to-end numbers from the client's records."""
    tokens = sum(s["tokens"] for s in steps if s["t1"] <= t_end)
    out = dict(tokens_per_s=tokens / (t_end - t0))
    gaps = []
    for t in tracked:
        ts = [x for x in t.times if open_loop_kind or x <= t_end]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    out["itl_p95_ms"] = 1e3 * _pct(gaps, 95) if gaps else None
    failed = 0
    if open_loop_kind:
        ttft = []
        for t in tracked:
            if t.times:
                ttft.append(t.times[0] - t.due)
            else:
                failed += 1
                ttft.append(drain_end - t.due)
        out["ttft_p50_ms"] = 1e3 * _pct(ttft, 50)
        out["ttft_p90_ms"] = 1e3 * _pct(ttft, 90)
        print("ttft ms: " + ", ".join(
            f"p{q} {1e3 * _pct(ttft, q):.3f}" for q in (50, 75, 90, 99)),
            file=sys.stderr)
    return out, failed


def check(params, c: dict, mix: dict, tracked, seed: int,
          control: bool) -> dict:
    """Compare the served tokens of a seeded sample of finished requests
    with the plain reference: the longest, and one drawn from each slot,
    so that every row of the batched decode is covered.  With
    ``control`` the fp8 reference is put in the program's place: at each
    position of the same prompts and served tokens, the token it puts
    first is judged instead of the served one."""
    done = [t for t in tracked if t.req.done]
    out = dict(requests=0, tokens=0, max_logit_gap=None)
    if not done:
        return out
    g = loadgen.rng(seed, 7)
    sample = [max(done, key=lambda t: len(t.req.out))]
    for slot in sorted({t.slot for t in done}):
        pool = [t for t in done if t.slot == slot and t not in sample]
        if pool:
            sample.append(pool[int(g.integers(len(pool)))])
    d = dims(c)
    length = mix["prompt"]["max"] + mix["output"]["max"]
    gaps = []
    for t in sample:
        toks = np.concatenate([t.req.prompt, np.asarray(t.req.out[:-1],
                                                        np.int32)])
        ref = np.asarray(model_ref.logits(params, d, toks, length))
        if control:
            lo = np.asarray(model_ref.logits(params, d, toks, length,
                                             quant="fp8"))
            gaps.append(model_ref.control_gaps(ref, lo, t.prompt_len,
                                               len(t.req.out)))
        else:
            gaps.append(model_ref.served_gaps(ref, t.prompt_len,
                                              t.req.out))
    gaps = np.concatenate(gaps)
    out.update(requests=len(sample), tokens=int(gaps.size),
               max_logit_gap=float(gaps.max()))
    return out


def run(cell: dict, c: dict, mix: dict, seed: int, seconds: float,
        tracer, clock, rate=None, control: bool = False) -> dict:
    """One run of a serving cell; ``tracer`` starts and stops the
    profiler around the window, ``clock`` records the set-up's end."""
    import jax

    from repro.core import warmstart

    eng, params, (lanes, loaded) = build(c, seed)
    vocab = dims(c)["vocab"]
    drv = Driver(eng)
    n = loadgen.request_count(mix, seconds, rate)
    warm(drv, loadgen.prompt_lengths(mix, n), seed, vocab)
    if loaded == 0:
        warmstart.save_lane_snapshot(lanes)
    open_kind = mix["kind"] == "open_loop"
    clock.setup_done()
    with tracer:
        with jax.profiler.TraceAnnotation("bench.window"):
            if open_kind:
                tracked, t0, t_end, late, drain_end = open_loop(
                    drv, mix, seed, seconds, vocab, rate=rate)
            else:
                tracked, t0, t_end = offline(drv, mix, seed, seconds, vocab)
                late, drain_end = [], t_end
    clock.window_done()
    steps = drv.steps
    window_steps = [s for s in steps if s["t1"] <= t_end]
    e2e, failed = latency_metrics(tracked, t0, t_end, drain_end, steps,
                                  open_kind)
    mem = clock.memory_peak()
    if late:
        print(f"generator lateness: p50 {1e3 * _pct(late, 50):.3f} ms, "
              f"max {1e3 * max(late):.3f} ms over {len(late)} sends",
              file=sys.stderr)
    queued = sum(1 for t in tracked if not t.times)
    print(f"window: {len(tracked)} requests, {len(window_steps)} steps, "
          f"{queued} without a first token at the end, "
          f"{sum(s['admit'] for s in window_steps)} admit steps, "
          f"{e2e['tokens_per_s']:.3f} tokens/s completed in the window",
          file=sys.stderr)
    eng.cache = None
    del eng, drv
    gc.collect()
    chk = check(params, c, mix, tracked, seed, control)
    limit = c["check"]["max_logit_gap"]
    # a configuration with no limit set from readings yet is not correct
    correct = (chk["max_logit_gap"] is not None and limit is not None
               and chk["max_logit_gap"] <= limit
               and chk["tokens"] >= mix["check_min_tokens"])
    checks = {"max_logit_gap": dict(value=chk["max_logit_gap"],
                                    limit=limit),
              "tokens_compared": dict(value=chk["tokens"],
                                      limit=mix["check_min_tokens"])}
    return dict(e2e=e2e, attempted=len(tracked), failed=failed,
                correct=bool(correct), checks=checks, memory=mem,
                window=(t0, t_end), steps=window_steps, config=c)
