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

A traced run traces the whole window, or, where the mix has a
``trace_sample`` (``start_s``, ``seconds``), only that stretch of it,
started and stopped at step boundaries on the driving thread: the
profiler keeps a bounded number of device events, and a model step with
many operations (a mixture of experts) fills it before the window ends.
"""
from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

import loadgen
import registry
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
    arch = registry.arch(c)
    params = make_params(c, loadgen.jax_seed(seed))
    jax.block_until_ready(params)
    lanes = os.path.join(LANES_DIR, c["name"])
    os.makedirs(lanes, exist_ok=True)
    loaded = warmstart.load_lane_snapshot(lanes)
    planner = OffloadPlanner(arch.arch_config(
        c, n_layers=sv["planner_layers"]))
    ctrl = OffloadController(planner, policy="per-step")
    eng = ServingEngine(arch.arch_config(c), params, slots=sv["slots"],
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
        self.sample = None          # (start, stop) on perf_counter, tracer

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
        if self.sample:
            (a, b), tracer = self.sample
            now = time.perf_counter()
            if now >= b:
                tracer.end()
                self.sample = None
            elif now >= a:
                tracer.begin()
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
    with the plain reference of the configuration's architecture
    (``logits`` in ``bench/arch/<arch>.py``): the longest, and one drawn
    from each slot, so that every row of the batched decode is covered.
    With ``control`` the fp8 reference is put in the program's place: at
    each position of the same prompts and served tokens, the token it
    puts first is judged instead of the served one."""
    done = [t for t in tracked if t.req.done]
    out = dict(requests=0, tokens=0, gaps=None)
    if not done:
        return out
    g = loadgen.rng(seed, 7)
    sample = [max(done, key=lambda t: len(t.req.out))]
    for slot in sorted({t.slot for t in done}):
        pool = [t for t in done if t.slot == slot and t not in sample]
        if pool:
            sample.append(pool[int(g.integers(len(pool)))])
    arch = registry.arch(c)
    length = mix["prompt"]["max"] + mix["output"]["max"]
    gaps = []
    for t in sample:
        toks = np.concatenate([t.req.prompt, np.asarray(t.req.out[:-1],
                                                        np.int32)])
        ref = np.asarray(arch.logits(params, c, toks, length))
        if control:
            lo = np.asarray(arch.logits(params, c, toks, length,
                                        quant="fp8"))
            gaps.append(model_ref.control_gaps(ref, lo, t.prompt_len,
                                               len(t.req.out)))
        else:
            gaps.append(model_ref.served_gaps(ref, t.prompt_len,
                                              t.req.out))
    gaps = np.concatenate(gaps)
    out.update(requests=len(sample), tokens=int(gaps.size), gaps=gaps)
    print(f"logit gaps over {gaps.size} tokens of {len(sample)} requests: "
          + ", ".join(f"{k} {v:.6f}" for k, v in gap_numbers(gaps).items())
          + f", mean {gaps.mean():.6f}", file=sys.stderr)
    return out


def gap_numbers(gaps: np.ndarray) -> dict:
    """The numbers a configuration's ``check`` may compare: the widest
    gap, and the 99th percentile, which the few tokens whose top logits
    the program and the reference order differently do not set."""
    return dict(max_logit_gap=float(gaps.max()),
                p99_logit_gap=float(np.percentile(gaps, 99)))


def run(cell: dict, c: dict, mix: dict, seed: int, seconds: float,
        tracer, clock, rate=None, control: bool = False) -> dict:
    """One run of a serving cell; ``tracer`` starts and stops the
    profiler around the window, ``clock`` records the set-up's end."""
    import jax

    from repro.core import warmstart

    eng, params, (lanes, loaded) = build(c, seed)
    vocab = registry.arch(c).vocab(c)
    drv = Driver(eng)
    n = loadgen.request_count(mix, seconds, rate)
    warm(drv, loadgen.prompt_lengths(mix, n), seed, vocab)
    if loaded == 0:
        warmstart.save_lane_snapshot(lanes)
    open_kind = mix["kind"] == "open_loop"
    sample = mix.get("trace_sample")
    tracer.sampled = sample is not None
    pauses = []         # Python's garbage collections in the window

    def gc_pause(phase, info, start=[0.0]):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - start[0])

    gc.callbacks.append(gc_pause)
    clock.setup_done()
    with tracer:
        with jax.profiler.TraceAnnotation("bench.window"):
            if sample:
                a = time.perf_counter() + sample["start_s"]
                drv.sample = ((a, a + sample["seconds"]), tracer)
            if open_kind:
                tracked, t0, t_end, late, drain_end = open_loop(
                    drv, mix, seed, seconds, vocab, rate=rate)
            else:
                tracked, t0, t_end = offline(drv, mix, seed, seconds, vocab)
                late, drain_end = [], t_end
    clock.window_done()
    gc.callbacks.remove(gc_pause)
    print(f"python gc in the window: {len(pauses)} collections, "
          f"{sum(pauses):.3f} s, longest {1e3 * max(pauses, default=0):.3f}"
          " ms", file=sys.stderr)
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
    admits = [s["t1"] - s["t0"] for s in window_steps if s["admit"]]
    decodes = sorted(((s["t1"] - s["t0"], s["i"]) for s in window_steps
                      if not s["admit"] and s["batch"]), reverse=True)
    print("slowest decode-only steps (ms, index): " + ", ".join(
        f"{1e3 * t:.3f} #{i}" for t, i in decodes[:5]), file=sys.stderr)
    dec_s = [t for t, _ in decodes] or [0.0]
    print(f"window: {len(tracked)} requests, {len(window_steps)} steps, "
          f"{queued} without a first token at the end, "
          f"{len(admits)} admit steps ({sum(admits):.3f} s), decode-only "
          f"steps p50 {1e3 * _pct(dec_s, 50):.3f} ms ({sum(dec_s):.3f} s), "
          f"{e2e['tokens_per_s']:.3f} tokens/s completed in the window",
          file=sys.stderr)
    eng.cache = None
    del eng, drv
    gc.collect()
    chk = check(params, c, mix, tracked, seed, control)
    numbers = {} if chk["gaps"] is None else gap_numbers(chk["gaps"])
    checks = {k: dict(value=numbers.get(k), limit=lim)
              for k, lim in c["check"].items()}
    # a configuration with no limit set from readings yet is not correct
    correct = (all(v["value"] is not None and v["limit"] is not None
                   and v["value"] <= v["limit"] for v in checks.values())
               and chk["tokens"] >= mix["check_min_tokens"])
    checks["tokens_compared"] = dict(value=chk["tokens"],
                                     limit=mix["check_min_tokens"])
    return dict(e2e=e2e, attempted=len(tracked), failed=failed,
                correct=bool(correct), checks=checks, memory=mem,
                window=(t0, t_end), steps=window_steps, config=c)
