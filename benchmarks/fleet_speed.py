"""Fleet-throughput benchmark: batched vs looped sweep resolution.

Comparisons over the full Fig. 4 grid (both axes, all dtypes, fence
on/off, PIM + baseline points):

* ``fleet/plan_*`` — the Python planning side alone: per-command
  ``StreamBuilder`` reference synthesis vs the vectorized block
  synthesizer (byte-identical streams, asserted).
* ``fleet/resolve_*`` — the execution core alone: per-point
  ``engine.run_streams`` loop vs one ``engine.resolve_fleet`` call on the
  same prebuilt streams (isolates the dispatch/batching win).
* ``fleet/sweep_*`` — end to end: a per-call ``run_gemv``/``run_baseline``
  loop vs one ``PimExecutor.run_many`` (includes stream building, which
  both paths share).
* ``fleet/specs_*`` — the spec-lifted facade: a (4 SystemSpec variants x
  shapes) design grid as per-variant executors + per-point calls vs ONE
  heterogeneous ``run_many`` fleet.
* ``fleet/specfam_*`` — the heterogeneous spec-family fleet
  (``configs/specfam.py``: phone-class LP5X / server parts /
  CXL-expander-like populations): per-family executors vs ONE batched
  ``run_many`` (cycle counts asserted bit-identical), then each
  family's offload frontier and speculative-decode economics as
  per-population rows.
* ``fleet/mesh_*`` — lane execution backends on the same prebuilt
  streams: the threaded per-device dispatch vs ONE ``shard_map``
  program per slab over a 1-D ``lanes`` mesh, at mesh sizes {1, 2, 4}
  (bounded by visible devices; bit-exactness asserted).
* ``fleet/pallas_*`` — the Pallas lane-resolver backend vs the scan
  resolver on the same prebuilt streams (bounded subset; interpret mode
  off TPU, so a parity/portability row; on TPU Mosaic refuses the kernel
  and the row reports ``unsupported``).
* ``fleet/serve_replan_*`` — repeated serving-loop telemetry queries
  (fresh planner per query, the replan pattern) with the resolved-lane
  LRU disabled vs enabled.
* ``fleet/policy_*`` — adaptive offload control closed-loop over a
  bursty serving trace: per-step recompute vs hysteresis vs sticky on
  control cost (us/step, planner queries) with the realized/oracle
  efficiency asserted >= 0.95.
* ``fleet/disagg_*`` — disaggregated prefill/decode serving: the
  model-free cell-pair simulator vs the monolithic queue model
  (us/tick, mirror parity asserted), policy efficiency over the
  bounded SLO-mixed pair's decode occupancy (>= 0.95 asserted), the
  peak KV-handoff depth vs its bound, and the warm-handoff lane
  account (zero re-resolves asserted).
* ``fleet/chaos_*`` — the degradation ladder on the resolve path: the
  same prebuilt points resolved healthy, under a transient top-rung
  fault (absorbed by bounded retry, backoff on a virtual clock — no
  real sleeps), and under a persistent top-rung fault (ladder
  step-down).  All three are asserted byte-identical to the looped
  oracle: degradation moves latency, never bytes.

The resolved-lane cache is cleared before every timed resolution section
so the ``resolve``/``sweep``/``specs`` rows measure real engine work on
both sides; ``serve_replan`` is the row that measures the cache itself.
Batched cycle counts are asserted bit-identical to the looped ones, so
the speedup rows in BENCH_*.json always track a correct result.

When run before JAX initializes, the process forces one XLA host device
per core (up to 4) so the engine's multi-device lane sharding is
exercised — the rows then measure the sharded fleet path with its
single-device fallback still covered by CI's default job.
"""
from __future__ import annotations

import sys

try:
    from ._xla_host_devices import force_host_devices
except ImportError:          # run as a script: benchmarks/ is sys.path[0]
    from _xla_host_devices import force_host_devices
force_host_devices()

import tempfile
import time

import numpy as np

from repro.core import engine, faults, warmstart
from repro.core.pimsim import PimSimulator
from repro.core.timing import DEFAULT_SYSTEM, LpddrTimings, SystemSpec
from repro.pimkernel.executor import GemvRequest, PimExecutor, spec_context
from repro.pimkernel.grids import FIG4_BASE as BASE
from repro.pimkernel.grids import FIG4_DIMS as DIMS
from repro.pimkernel.grids import fig4_grid
from repro.pimkernel.tileconfig import PimDType

QUICK_DIMS = [512, 1024, 2048]

def main(quick: bool = False) -> dict:
    # The launchers' persistent caches: compile cache always, lane
    # snapshot when REPRO_CACHE_DIR is set.
    warmstart.enable_compilation_cache()
    warmstart.enable_warm_start()
    dims = QUICK_DIMS if quick else DIMS
    ex = PimExecutor(DEFAULT_SYSTEM)
    reqs = fig4_grid(dims)
    n = len(reqs)

    # ---- planning: vectorized block synthesis vs StreamBuilder oracle --
    pim_reqs = [r for r in reqs if r.kind == "pim"]
    plans = [ex.plan(r.H, r.W, r.dtype, reshape=r.reshape) for r in pim_reqs]

    t0 = time.perf_counter()
    ref_streams = [
        spec_context(layout.spec).kernel.build_reference(
            layout, program, fence=r.fence, flush=r.flush)
        for r, (layout, program) in zip(pim_reqs, plans)]
    plan_ref_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    vec_streams = [
        spec_context(layout.spec).kernel.build(
            layout, program, fence=r.fence, flush=r.flush)
        for r, (layout, program) in zip(pim_reqs, plans)]
    plan_vec_s = time.perf_counter() - t0

    for gr, gv in zip(ref_streams, vec_streams):
        for a, b in zip(gr.streams, gv.streams):
            np.testing.assert_array_equal(a, b)

    m_pim = len(pim_reqs)
    print(f"fleet/plan_reference,{plan_ref_s*1e6/m_pim:.1f},"
          f"{m_pim/plan_ref_s:.1f}")
    print(f"fleet/plan_vectorized,{plan_vec_s*1e6/m_pim:.1f},"
          f"{m_pim/plan_vec_s:.1f}")
    print(f"fleet/plan_speedup,{plan_vec_s*1e3:.1f},"
          f"{plan_ref_s/plan_vec_s:.1f}")

    # Build all streams once; both resolve paths time the same arrays.
    planned = ex.plan_many(reqs)
    cyc = planned[0].ctx.cyc
    points = [(p.ctx.cyc, p.streams) for p in planned]

    # Warm the compile caches of both paths (compilation is a one-time
    # cost shared across every spec variant; we measure steady state).
    engine.run_streams(cyc, planned[0].streams)
    engine.resolve_fleet(points)

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    looped = [engine.run_streams(p.ctx.cyc, p.streams)[1] for p in planned]
    resolve_loop_s = time.perf_counter() - t0

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    fleet = engine.resolve_fleet(points)
    resolve_batch_s = time.perf_counter() - t0

    for solo, fr in zip(looped, fleet):
        np.testing.assert_array_equal(solo, fr.totals)

    print(f"fleet/resolve_looped,{resolve_loop_s*1e6/n:.1f},"
          f"{n/resolve_loop_s:.1f}")
    print(f"fleet/resolve_batched,{resolve_batch_s*1e6/n:.1f},"
          f"{n/resolve_batch_s:.1f}")
    print(f"fleet/resolve_speedup,{resolve_batch_s*1e3:.1f},"
          f"{resolve_loop_s/resolve_batch_s:.1f}")

    # Mesh lane execution: the same prebuilt streams resolved by the
    # threaded per-device dispatch (the resolve_batched row above) vs
    # ONE shard_map program per bucketed slab, at every mesh size the
    # visible devices allow.  Cycle counts are asserted bit-identical,
    # so the mesh rows always track a correct backend.
    mesh_sizes = [m for m in (1, 2, 4)
                  if m <= len(engine.lane_devices())]
    mesh_row_s: dict[int, float] = {}
    for m in mesh_sizes:
        with engine.lane_mesh_scope(m):
            engine.lane_cache_clear()           # else warm-up is LRU hits
            engine.resolve_fleet(points)        # warm the mesh compiles
            engine.lane_cache_clear()
            t0 = time.perf_counter()
            meshed = engine.resolve_fleet(points)
            mesh_row_s[m] = time.perf_counter() - t0
        for solo, fr in zip(looped, meshed):
            np.testing.assert_array_equal(solo, fr.totals)
        print(f"fleet/mesh_shardmap_{m},{mesh_row_s[m]*1e6/n:.1f},"
              f"{n/mesh_row_s[m]:.1f}")
    mesh_best_s = min(mesh_row_s.values())
    print(f"fleet/mesh_threaded,{resolve_batch_s*1e6/n:.1f},"
          f"{n/resolve_batch_s:.1f}")
    print(f"fleet/mesh_speedup,{mesh_best_s*1e3:.1f},"
          f"{resolve_batch_s/mesh_best_s:.1f}")

    # Pallas lane resolver vs the scan resolver on the same prebuilt
    # streams (a bounded subset — off TPU the kernel runs under the
    # Pallas *interpreter*, so the row is an honest parity/portability
    # report, not a speed claim; on TPU Mosaic refuses the kernel today).
    # Bit-exactness asserted like every other row.
    from repro.kernels import lane_scan
    pallas_speedup = None
    if lane_scan.pallas_lane_supported():
        sub = points[: min(8, n)]
        ns = len(sub)
        engine.lane_cache_clear()
        engine.resolve_fleet(sub)               # scan path warm
        engine.lane_cache_clear()
        t0 = time.perf_counter()
        scan_res = engine.resolve_fleet(sub)
        pallas_scan_s = time.perf_counter() - t0
        with engine.lane_backend_scope("pallas"):
            engine.lane_cache_clear()
            engine.resolve_fleet(sub)           # warm the kernel compiles
            engine.lane_cache_clear()
            t0 = time.perf_counter()
            pallas_res = engine.resolve_fleet(sub)
            pallas_kernel_s = time.perf_counter() - t0
        for a, b in zip(scan_res, pallas_res):
            np.testing.assert_array_equal(a.totals, b.totals)
        pallas_speedup = pallas_scan_s / pallas_kernel_s
        print(f"fleet/pallas_scan,{pallas_scan_s*1e6/ns:.1f},"
              f"{ns/pallas_scan_s:.1f}")
        print(f"fleet/pallas_kernel,{pallas_kernel_s*1e6/ns:.1f},"
              f"{ns/pallas_kernel_s:.1f}")
        print(f"fleet/pallas_speedup,{pallas_kernel_s*1e3:.1f},"
              f"{pallas_speedup:.2f}")
    else:
        print("fleet/pallas_kernel,unsupported,0.0")

    # End to end: fresh executors so neither path reuses built streams.
    # Warm the keyed fleet path too (its dedupe can produce slab shapes
    # the unkeyed warm-up above never compiled).
    PimExecutor(DEFAULT_SYSTEM).run_many(reqs)
    ex_loop = PimExecutor(DEFAULT_SYSTEM)
    engine.lane_cache_clear()
    t0 = time.perf_counter()
    solo_res = [
        ex_loop.run_gemv(r.H, r.W, r.dtype, fence=r.fence,
                         reshape=r.reshape, flush=r.flush)
        if r.kind == "pim" else
        ex_loop.run_baseline(r.H, r.W, r.dtype)
        for r in reqs]
    sweep_loop_s = time.perf_counter() - t0

    ex_batch = PimExecutor(DEFAULT_SYSTEM)
    engine.lane_cache_clear()
    t0 = time.perf_counter()
    batch_res = ex_batch.run_many(reqs)
    sweep_batch_s = time.perf_counter() - t0

    for a, b in zip(solo_res, batch_res):
        assert a.cycles == b.cycles, (a.meta, a.cycles, b.cycles)

    print(f"fleet/sweep_looped,{sweep_loop_s*1e6/n:.1f},"
          f"{n/sweep_loop_s:.1f}")
    print(f"fleet/sweep_batched,{sweep_batch_s*1e6/n:.1f},"
          f"{n/sweep_batch_s:.1f}")
    print(f"fleet/sweep_speedup,{sweep_batch_s*1e3:.1f},"
          f"{sweep_loop_s/sweep_batch_s:.1f}")

    # Spec-lifted facade: a heterogeneous (spec x shape x kind) design
    # grid through one run_many vs per-variant executors.
    specs = [DEFAULT_SYSTEM] + [
        SystemSpec(timings=LpddrTimings(tRCD=20.0 + 2 * i,
                                        tRP=20.0 + 2 * i))
        for i in range(3)]
    grid = [r for sp in specs for d in dims
            for r in (GemvRequest.pim(BASE, d, PimDType.W8A8, spec=sp),
                      GemvRequest.baseline(BASE, d, PimDType.W8A8,
                                           spec=sp))]
    m = len(grid)
    PimExecutor().run_many(grid)     # warm the heterogeneous slab shapes

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    spec_loop = []
    for sp in specs:
        ex_sp = PimExecutor(sp)
        spec_loop += [ex_sp.run_gemv(r.H, r.W, r.dtype)
                      if r.kind == "pim" else
                      ex_sp.run_baseline(r.H, r.W, r.dtype)
                      for r in grid if r.spec == sp]
    specs_loop_s = time.perf_counter() - t0

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    spec_batch = PimExecutor().run_many(grid)
    specs_batch_s = time.perf_counter() - t0

    for a, b in zip(spec_loop, spec_batch):
        assert a.cycles == b.cycles

    print(f"fleet/specs_looped,{specs_loop_s*1e6/m:.1f},"
          f"{m/specs_loop_s:.1f}")
    print(f"fleet/specs_batched,{specs_batch_s*1e6/m:.1f},"
          f"{m/specs_batch_s:.1f}")
    print(f"fleet/specs_speedup,{specs_batch_s*1e3:.1f},"
          f"{specs_loop_s/specs_batch_s:.1f}")

    # Heterogeneous spec-family fleets: the configs/specfam.py
    # populations (phone-class LP5X, server parts, a CXL-expander-like
    # latency profile) as one design grid — per-family executors +
    # per-point calls vs ONE batched run_many over the whole population,
    # cycle counts asserted bit-identical.  Then each family's offload
    # frontier and draft-model speculative-decode economics become
    # per-population rows (cache lookups + arithmetic after a single
    # plan_grid dispatch).
    from repro.configs import ARCHS
    from repro.configs.specfam import SPEC_FAMILIES
    from repro.serving.offload import OffloadPlanner
    fam_grid = [r for sp in SPEC_FAMILIES.values() for d in dims
                for r in (GemvRequest.pim(BASE, d, PimDType.W8A8, spec=sp),
                          GemvRequest.baseline(BASE, d, PimDType.W8A8,
                                               spec=sp))]
    fm = len(fam_grid)
    PimExecutor().run_many(fam_grid)  # warm heterogeneous slab shapes

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    fam_loop = []
    for sp in SPEC_FAMILIES.values():
        ex_fam = PimExecutor(sp)
        fam_loop += [ex_fam.run_gemv(r.H, r.W, r.dtype)
                     if r.kind == "pim" else
                     ex_fam.run_baseline(r.H, r.W, r.dtype)
                     for r in fam_grid if r.spec == sp]
    specfam_loop_s = time.perf_counter() - t0

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    fam_batch = PimExecutor().run_many(fam_grid)
    specfam_batch_s = time.perf_counter() - t0

    for a, b in zip(fam_loop, fam_batch):
        assert a.cycles == b.cycles, (a.meta, a.cycles, b.cycles)

    print(f"fleet/specfam_looped,{specfam_loop_s*1e6/fm:.1f},"
          f"{fm/specfam_loop_s:.1f}")
    print(f"fleet/specfam_batched,{specfam_batch_s*1e6/fm:.1f},"
          f"{fm/specfam_batch_s:.1f}")
    print(f"fleet/specfam_speedup,{specfam_batch_s*1e3:.1f},"
          f"{specfam_loop_s/specfam_batch_s:.1f}")

    fam_planner = OffloadPlanner(ARCHS["mamba2-130m"], PimSimulator())
    fam_planner.plan_grid(list(SPEC_FAMILIES.values()))
    specfam_spec_decode = {}
    for fam_name, sp in SPEC_FAMILIES.items():
        frontier = fam_planner.frontier(spec=sp)
        sdrec = fam_planner.spec_decode_speedup(spec=sp)
        specfam_spec_decode[fam_name] = sdrec["speedup"]
        n_pim = sum(1 for b in frontier.values() if b > 1)
        print(f"fleet/specfam_{fam_name},{n_pim}/{len(frontier)},"
              f"{sdrec['speedup']:.2f}")

    # Serving replan loop: fresh planner per query (so the planner's own
    # plan cache cannot hide engine work), resolved-lane LRU off vs on.
    from repro.configs import ARCHS
    from repro.serving.offload import OffloadPlanner
    cfg = ARCHS["mamba2-130m"]
    reps = 2

    def replan_once() -> float:
        return OffloadPlanner(cfg, PimSimulator()).decode_speedup(
            batch=4)["speedup"]

    engine.configure_lane_cache(0)          # disabled
    replan_once()                           # warm engine compiles
    t0 = time.perf_counter()
    cold = [replan_once() for _ in range(reps)]
    replan_cold_s = (time.perf_counter() - t0) / reps

    engine.configure_lane_cache(4096)       # enabled, then warmed
    replan_once()
    t0 = time.perf_counter()
    warm = [replan_once() for _ in range(reps)]
    replan_warm_s = (time.perf_counter() - t0) / reps

    assert cold == warm, "lane cache must not change telemetry results"

    print(f"fleet/serve_replan_cold,{replan_cold_s*1e6:.1f},"
          f"{1/replan_cold_s:.2f}")
    print(f"fleet/serve_replan_cached,{replan_warm_s*1e6:.1f},"
          f"{1/replan_warm_s:.2f}")
    print(f"fleet/serve_replan_speedup,{replan_warm_s*1e3:.1f},"
          f"{replan_cold_s/replan_warm_s:.1f}")

    # Adaptive offload control: each policy closed-loop over the same
    # bursty serving trace (simulated occupancy, fresh planner per
    # policy so the plan cost is inside the measurement).  Columns:
    # us per decode step, planner queries issued.  The efficiency row
    # asserts the cheap policies stay >= 0.95x of the per-step oracle —
    # the rows always track a correct control loop, same discipline as
    # the bit-exactness asserts above.
    from repro.serving.scenarios import DisaggConfig, assign_slo, \
        make_scenario, occupancy_trace, run_policy_over_trace, \
        simulate_batches, simulate_disagg
    trace = occupancy_trace(make_scenario("bursty", seed=7, quick=quick))
    policy_reports = {}
    policy_step_us = {}
    for pol in ("per-step", "hysteresis", "sticky"):
        planner_pol = OffloadPlanner(cfg, PimSimulator())
        t0 = time.perf_counter()
        controller = run_policy_over_trace(planner_pol, pol, trace)
        dt = time.perf_counter() - t0
        rep = controller.report()
        policy_reports[pol] = rep
        policy_step_us[pol] = dt * 1e6 / max(rep["steps"], 1)
        print(f"fleet/policy_{pol},{policy_step_us[pol]:.1f},"
              f"{rep['planner_queries']}")
    per_step = policy_reports["per-step"]
    assert abs(per_step["efficiency"] - 1.0) < 1e-12, \
        "per-step recompute must be its own oracle"
    for pol in ("hysteresis", "sticky"):
        rep = policy_reports[pol]
        assert rep["efficiency"] >= 0.95, (pol, rep["efficiency"])
        assert rep["planner_queries"] < per_step["planner_queries"], \
            (pol, rep["planner_queries"])
    print(f"fleet/policy_efficiency,"
          f"{policy_reports['hysteresis']['efficiency']:.4f},"
          f"{policy_reports['sticky']['efficiency']:.4f}")

    # Disaggregated serving: the model-free cell-pair simulator vs the
    # monolithic queue model on the same bursty workload (us/tick;
    # mirror parity asserted, so the rows always track the pinned
    # scheduling semantics), then the policy closed loop over the
    # bounded SLO-mixed pair's decode occupancy with the efficiency
    # floor, the handoff bound and warm-handoff lane accounting all
    # asserted.
    spec_d = make_scenario("bursty", seed=7, quick=quick)
    reps_d = 20
    t0 = time.perf_counter()
    for _ in range(reps_d):
        mono_batches = simulate_batches(spec_d)
    disagg_mono_s = (time.perf_counter() - t0) / reps_d
    t0 = time.perf_counter()
    for _ in range(reps_d):
        mirror_sim = simulate_disagg(spec_d)
    disagg_cells_s = (time.perf_counter() - t0) / reps_d
    assert mirror_sim["per_tick_batch"] == mono_batches, \
        "mirror cells must replay the monolithic queue model"
    ticks = len(mono_batches)
    print(f"fleet/disagg_sim_mono,{disagg_mono_s*1e6/ticks:.2f},"
          f"{ticks/disagg_mono_s:.0f}")
    print(f"fleet/disagg_sim_cells,{disagg_cells_s*1e6/ticks:.2f},"
          f"{ticks/disagg_cells_s:.0f}")

    dcfg = DisaggConfig(prefill_budget=2, handoff_bound=3,
                        starvation_age=4)
    dsim = simulate_disagg(spec_d, dcfg, assign_slo(spec_d, 0.6))
    assert dsim["max_handoff_depth"] <= dcfg.handoff_bound, \
        "KV-handoff bound overrun"
    dec_trace = [b for b in dsim["per_tick_batch"] if b > 0]
    disagg_eff = {}
    for pol in ("hysteresis", "sticky"):
        rep = run_policy_over_trace(OffloadPlanner(cfg, PimSimulator()),
                                    pol, dec_trace).report()
        assert rep["efficiency"] >= 0.95, (pol, rep["efficiency"])
        disagg_eff[pol] = rep["efficiency"]
    print(f"fleet/disagg_efficiency,{disagg_eff['hysteresis']:.4f},"
          f"{disagg_eff['sticky']:.4f}")
    print(f"fleet/disagg_handoff,{dsim['max_handoff_depth']},"
          f"{dcfg.handoff_bound}")

    # Warm handoff does zero lane re-resolves: once the planner's fleet
    # query has populated the lane LRU, serving the whole disagg trace
    # adds no misses.
    warm_planner = OffloadPlanner(cfg, PimSimulator())
    warm_planner.plan()
    before_misses = engine.lane_cache_info()["misses"]
    run_policy_over_trace(warm_planner, "hysteresis", dec_trace)
    new_misses = engine.lane_cache_info()["misses"] - before_misses
    assert new_misses == 0, \
        f"warm disagg serve re-resolved {new_misses} lanes"
    print(f"fleet/disagg_lane_resolves,{new_misses},{len(dec_trace)}")

    # Serve-daemon economics (model-free): the autoscaled cell pair vs
    # the fixed-slot oracle on the same bounded SLO-mixed workload.
    # Efficiency = decode work served per slot-tick PROVISIONED — the
    # fixed oracle provisions slots x ticks, the autoscaler only what
    # its limit trace admits — asserted >= 0.95x the oracle (in
    # practice well above 1: idle slots are the oracle's waste).  The
    # streamed-trace writer is timed per record with its chunk
    # reassembly asserted equal, so the daemon rows always track a
    # correct trace path.
    from repro.serving.daemon import TraceWriter
    from repro.serving.scenarios import AutoscaleConfig
    auto_cfg = AutoscaleConfig(min_slots=1)
    slo_d = assign_slo(spec_d, 0.6)
    t0 = time.perf_counter()
    for _ in range(reps_d):
        auto_sim = simulate_disagg(spec_d, dcfg, slo_d,
                                   autoscale=auto_cfg)
    daemon_auto_s = (time.perf_counter() - t0) / reps_d
    fixed_sim = simulate_disagg(spec_d, dcfg, slo_d)
    auto_ticks = len(auto_sim["per_tick_batch"])
    auto_eff = sum(auto_sim["per_tick_batch"]) / sum(auto_sim["limits"])
    fixed_eff = (sum(fixed_sim["per_tick_batch"])
                 / (spec_d.slots * len(fixed_sim["per_tick_batch"])))
    daemon_eff_ratio = auto_eff / fixed_eff
    assert daemon_eff_ratio >= 0.95, \
        f"autoscale efficiency {daemon_eff_ratio:.3f}x below the oracle"
    assert set(auto_sim["completion_ticks"]) == \
        set(fixed_sim["completion_ticks"]), \
        "autoscale must complete the same request set"
    print(f"fleet/daemon_sim_autoscale,{daemon_auto_s*1e6/auto_ticks:.2f},"
          f"{auto_ticks/daemon_auto_s:.0f}")
    print(f"fleet/daemon_autoscale_efficiency,{daemon_eff_ratio:.2f},"
          f"{auto_ticks/len(fixed_sim['per_tick_batch']):.2f}")

    with tempfile.TemporaryDirectory(prefix="repro-daemon-") as tdir:
        path = f"{tdir}/trace.jsonl"
        writer = TraceWriter(path, chunk_records=256)
        writer.write_meta(scenario=spec_d.to_record(), policy="bench",
                          fence=True)
        t0 = time.perf_counter()
        for tick, b in enumerate(auto_sim["per_tick_batch"]):
            writer.write_tick(tick, b)
        writer.write_summary(dict(limits=auto_sim["limits"]))
        writer.close()
        daemon_stream_s = time.perf_counter() - t0
        loaded = TraceWriter.load(path)
    assert loaded["per_tick_batch"] == auto_sim["per_tick_batch"], \
        "streamed chunks must reassemble the exact tick trace"
    print(f"fleet/daemon_stream,{daemon_stream_s*1e6/auto_ticks:.2f},"
          f"{writer.flushes}")

    # Chaos: the degradation ladder on the fleet resolve path.  The
    # same prebuilt points resolve three ways — healthy; under a
    # transient top-rung fault (absorbed by one bounded retry, backoff
    # on a VirtualClock so the row never real-sleeps); and, when the
    # ladder has a lower rung, under a persistent top-rung fault that
    # steps the resolve down.  Every variant's totals are asserted
    # byte-identical to the looped oracle: degradation moves latency,
    # never bytes.
    ladder = engine.ladder_rungs()
    top_site = f"backend.{ladder[0]}"
    engine.lane_cache_clear()
    t0 = time.perf_counter()
    chaos_healthy = engine.resolve_fleet(points)
    chaos_healthy_s = time.perf_counter() - t0
    for solo, fr in zip(looped, chaos_healthy):
        np.testing.assert_array_equal(solo, fr.totals)

    faults.reset()
    inj = faults.FaultInjector()
    inj.arm(top_site, count=1, message="benchmark transient")
    engine.lane_cache_clear()
    with faults.fault_scope(inj), \
            faults.retry_scope(clock=faults.VirtualClock()):
        t0 = time.perf_counter()
        absorbed = engine.resolve_fleet(points)
        chaos_absorbed_s = time.perf_counter() - t0
    kinds = [e["kind"] for e in faults.events()]
    assert "retry" in kinds, "transient fault was never retried"
    assert "degrade" not in kinds, "transient fault must not step down"
    for solo, fr in zip(looped, absorbed):
        np.testing.assert_array_equal(solo, fr.totals)

    chaos_degraded_s = None
    if len(ladder) > 1:
        faults.reset()
        inj = faults.FaultInjector()
        inj.arm(top_site, count=-1, message="benchmark persistent")
        engine.lane_cache_clear()
        with faults.fault_scope(inj), \
                faults.retry_scope(clock=faults.VirtualClock()):
            t0 = time.perf_counter()
            degraded = engine.resolve_fleet(points)
            chaos_degraded_s = time.perf_counter() - t0
        n_degrades = sum(1 for e in faults.events()
                         if e["kind"] == "degrade")
        assert n_degrades >= 1, "persistent fault never stepped down"
        for solo, fr in zip(looped, degraded):
            np.testing.assert_array_equal(solo, fr.totals)
    faults.reset()

    print(f"fleet/chaos_healthy,{chaos_healthy_s*1e6/n:.1f},"
          f"{n/chaos_healthy_s:.1f}")
    print(f"fleet/chaos_absorbed,{chaos_absorbed_s*1e6/n:.1f},"
          f"{chaos_absorbed_s/chaos_healthy_s:.2f}")
    if chaos_degraded_s is not None:
        print(f"fleet/chaos_degraded,{chaos_degraded_s*1e6/n:.1f},"
              f"{chaos_degraded_s/chaos_healthy_s:.2f}")
    else:
        print("fleet/chaos_degraded,terminal_rung_only,1.00")

    return dict(points=n,
                devices=len(engine.lane_devices()),
                plan_speedup=plan_ref_s / plan_vec_s,
                resolve_speedup=resolve_loop_s / resolve_batch_s,
                mesh_sizes=mesh_sizes,
                mesh_speedup=resolve_batch_s / mesh_best_s,
                mesh_step_us={m: s * 1e6 / n
                              for m, s in mesh_row_s.items()},
                sweep_speedup=sweep_loop_s / sweep_batch_s,
                specs_speedup=specs_loop_s / specs_batch_s,
                specfam_speedup=specfam_loop_s / specfam_batch_s,
                specfam_families=list(SPEC_FAMILIES),
                specfam_spec_decode=specfam_spec_decode,
                serve_replan_speedup=replan_cold_s / replan_warm_s,
                pallas_speedup=pallas_speedup,
                policy_efficiency={p: r["efficiency"]
                                   for p, r in policy_reports.items()},
                policy_queries={p: r["planner_queries"]
                                for p, r in policy_reports.items()},
                policy_step_us=policy_step_us,
                disagg_sim_mono_tick_us=disagg_mono_s * 1e6 / ticks,
                disagg_sim_cells_tick_us=disagg_cells_s * 1e6 / ticks,
                disagg_efficiency=disagg_eff,
                disagg_max_handoff_depth=dsim["max_handoff_depth"],
                disagg_lane_resolves=new_misses,
                daemon_autoscale_efficiency=daemon_eff_ratio,
                daemon_sim_tick_us=daemon_auto_s * 1e6 / auto_ticks,
                daemon_stream_record_us=daemon_stream_s * 1e6 / auto_ticks,
                chaos_ladder=ladder,
                chaos_absorbed_overhead=chaos_absorbed_s / chaos_healthy_s,
                chaos_degraded_overhead=(
                    chaos_degraded_s / chaos_healthy_s
                    if chaos_degraded_s is not None else None),
                plan_batched_s=plan_vec_s,
                sweep_batched_s=sweep_batch_s,
                sweep_looped_s=sweep_loop_s)


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
