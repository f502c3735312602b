"""Design-sweep cells: ``PimExecutor.run_many`` over a model's decode GEMV
shapes on every device family of the mix, one new design point per sweep.

Each sweep scales every family's named core timings by a factor drawn
from the seed, so every sweep is a design point the resolved-lane cache
has not seen: each sweep starts cold with no artificial clear.  Set-up
runs one sweep at a design point of its own, which compiles or loads
every resolver shape the window uses.  The window runs whole sweeps, and
starts another only while the mean sweep so far would end inside
``--seconds``.  Each ``run_many`` call is one host span (``bench.sweep``,
numbered).  A traced run traces only a sample that starts at the
boundary before sweep ``trace_sample["sweep"]`` and lasts
``trace_sample["seconds"]``: the host path of that sweep (stream
synthesis, dedupe, slab packing, transfer) and the start of its
resolver scan.  The TPU profiler records every iteration of the scan
(~6 M events a second) and keeps only ~1.2 s of them.

The check, after the window, covers one seeded sweep with three numbers,
each with the limit 0: answers (a request's cycles) that differ from the
plain reference resolving the very streams the timed ``run_many``
planned; requests whose weight commands differ from an independent
count (``counts.gemv_weight_commands``); and records of the golden grid
that differ from ``reference/golden_fleet.json``.
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time

import repro.core  # noqa: F401 - before repro.pimkernel (import cycle)
from repro.pimkernel.executor import GemvRequest, PimExecutor

import loadgen
import registry
from counts import gemv_weight_commands, sweep_counts
from reference import pim_ref

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "reference", "golden_fleet.json")


def design_point(mix: dict, scales: dict) -> dict:
    """Family parameters of one sweep (nanosecond timings scaled)."""
    out = {}
    for name, fam in mix["families"].items():
        f = copy.deepcopy(fam)
        for key in mix["scale_fields"]:
            f["timings"][key] = fam["timings"][key] * scales[name]
        out[name] = f
    return out


def requests(c: dict, mix: dict, point: dict):
    """The sweep's requests, in order, with (family, shape, variant)."""
    from repro.core.timing import LpddrTimings, PimSpec, SystemSpec
    from repro.pimkernel.tileconfig import PimDType

    dt = PimDType[mix["dtype"]]
    reqs, meta = [], []
    for name, fam in point.items():
        spec = SystemSpec(timings=LpddrTimings(**fam["timings"]),
                          pim=PimSpec(**fam["pim"]),
                          num_channels=fam["num_channels"],
                          num_ranks=fam["num_ranks"],
                          fence_ns=fam["fence_ns"],
                          refresh_enabled=fam["refresh_enabled"])
        for h, w in registry.arch(c).gemv_shapes(c):
            for var in mix["variants"]:
                if var["kind"] == "pim":
                    reqs.append(GemvRequest.pim(
                        h, w, dt, fence=var["fence"],
                        reshape=h < mix["reshape_below"], spec=spec))
                else:
                    reqs.append(GemvRequest.baseline(h, w, dt, spec=spec))
                meta.append((name, h, w, var["kind"]))
    return reqs, meta


class Recording(PimExecutor):
    """The program's executor, keeping what its last ``run_many``
    planned: the streams the timed call resolved."""

    planned = None

    def plan_many(self, reqs):
        self.planned = super().plan_many(reqs)
        return self.planned


def reference_answers(point: dict, reqs, meta, planned,
                      drop: str | None = None) -> list[int]:
    """Each request's cycles by the plain reference: every channel
    stream the timed call planned for it, resolved under the cycles the
    reference derives itself from the family's numbers."""
    cyc = {name: pim_ref.cycles(fam) for name, fam in point.items()}
    by_key = {p.req.key: p for p in planned}
    memo: dict = {}
    out = []
    for (name, *_), r in zip(meta, reqs):
        totals = []
        for st in by_key[r.key].streams:
            key = (name, st.shape[0], st.tobytes())
            if key not in memo:
                memo[key] = pim_ref.total_cycles(cyc[name], st, drop)
            totals.append(memo[key])
        out.append(max(totals, default=0))
    return out


def count_mismatches(point: dict, reqs, meta, results, mix: dict) -> int:
    """Requests whose resolved streams carry another number of weight
    commands than the independent count."""
    bad = 0
    for (name, h, w, kind), r, res in zip(meta, reqs, results):
        want = gemv_weight_commands(kind, h, w, mix["dtype"], point[name],
                                    reshape=r.reshape)
        if any(int(res.counts[op]) != n for op, n in want.items()):
            bad += 1
    return bad


def golden_requests() -> list[tuple[str, GemvRequest]]:
    """The golden parity grid: two memory systems x four shapes."""
    from repro.core.timing import LpddrTimings, PimSpec, SystemSpec

    specs = {"lp5x-9600": SystemSpec(),
             "rcd24-mac2": SystemSpec(timings=LpddrTimings(tRCD=24.0),
                                      pim=PimSpec(mac_interval_ck=2))}
    shapes = [("pim", 256, 1024, "W8A8", False, False),
              ("pim", 512, 2048, "W8A16", True, False),
              ("pim", 1024, 512, "W4A8", False, True),
              ("base", 1024, 1024, "W8A8", False, False)]
    out = []
    for sname, sp in specs.items():
        for kind, h, w, dt, f, r in shapes:
            label = (f"{sname}/{kind}-{h}x{w}-{dt}" + ("-fence" if f else "")
                     + ("-reshape" if r else ""))
            out.append((label, GemvRequest.pim(h, w, dt, fence=f,
                                               reshape=r, spec=sp)
                        if kind == "pim" else
                        GemvRequest.baseline(h, w, dt, spec=sp)))
    return out


def golden_mismatches() -> int:
    """Records of the golden grid, run through ``run_many``, that differ
    from the pinned file in any field."""
    with open(GOLDEN, encoding="utf-8") as f:
        want = json.load(f)
    grid = golden_requests()
    res = PimExecutor().run_many([r for _, r in grid])
    got = json.loads(json.dumps({
        label: dict(cycles=x.cycles, ns=x.ns, flops=x.flops,
                    weight_bytes=x.weight_bytes,
                    utilization=x.utilization, split=x.split,
                    counts=[int(c) for c in x.counts], energy=x.energy)
        for (label, _), x in zip(grid, res)}))
    return sum(got.get(k) != v for k, v in want.items())


def run(cell: dict, c: dict, mix: dict, seed: int, seconds: float,
        tracer, clock, control: bool = False) -> dict:
    import jax

    warm_point = design_point(mix, loadgen.sweep_scales(mix, seed, -1))
    PimExecutor().run_many(requests(c, mix, warm_point)[0])
    clock.setup_done()
    sweeps = []
    tracer.sampled = True
    sample = mix["trace_sample"]
    with tracer:
        with jax.profiler.TraceAnnotation("bench.sweeps"):
            t0 = time.perf_counter()
            k = 0
            while True:
                point = design_point(mix, loadgen.sweep_scales(mix, seed, k))
                reqs, meta = requests(c, mix, point)
                if k == sample["sweep"]:
                    tracer.sample(sample["seconds"])
                ex = Recording()
                with jax.profiler.TraceAnnotation("bench.sweep", i=k):
                    a = time.perf_counter()
                    res = ex.run_many(reqs)
                    b = time.perf_counter()
                sweeps.append(dict(i=k, t0=a, t1=b, point=point,
                                   reqs=reqs, meta=meta, results=res,
                                   planned=ex.planned))
                k += 1
                # start another sweep only if it should end in the window
                if (b - t0) * (k + 1) / k > seconds:
                    break
            t_end = time.perf_counter()
    clock.window_done()
    mem = clock.memory_peak()
    for s in sweeps:
        s.update(sweep_counts(s["planned"]))
    cmds = sum(s["simulated"] for s in sweeps)
    host = sum(s["t1"] - s["t0"] for s in sweeps)
    e2e = dict(sim_cmds_per_s=cmds / host)
    print(f"window: {len(sweeps)} sweeps, {cmds} simulated commands "
          f"({sweeps[0]['simulated']} a sweep, {sweeps[0]['resolved']} "
          f"resolved in {sweeps[0]['lanes']} lanes), "
          f"{host:.6f} s in run_many of {t_end - t0:.6f} s",
          file=sys.stderr)
    pick = sweeps[int(loadgen.rng(seed, 7).integers(len(sweeps)))]
    args = (pick["point"], pick["reqs"], pick["meta"], pick["planned"])
    ref = reference_answers(*args)
    if control:
        # the reference without tRCD, put in the program's place
        answers = reference_answers(*args, drop="RCD")
    else:
        answers = [r.cycles for r in pick["results"]]
    lim = mix["check"]
    checks = {
        "mismatched_answers": sum(a != b for a, b in zip(answers, ref)),
        "mismatched_counts": count_mismatches(
            pick["point"], pick["reqs"], pick["meta"], pick["results"], mix),
        "mismatched_golden": golden_mismatches()}
    checks = {k: dict(value=int(v), limit=lim[k]) for k, v in checks.items()}
    print(f"check: sweep {pick['i']}: {len(answers)} answers", file=sys.stderr)
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    return dict(e2e=e2e, attempted=len(sweeps) * len(sweeps[0]["reqs"]),
                failed=0, correct=correct, checks=checks, memory=mem,
                window=(t0, t_end), sweeps=sweeps, config=c)
