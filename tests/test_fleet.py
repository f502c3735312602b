"""Fleet execution core: batched parity, dedupe, and compile behavior.

The contract of the fleet request API: ``run_many`` is bit-identical to
the per-call paths, the result cache dedupes across query styles, points
with *different* ``SystemSpec`` timing resolve correctly inside one fleet
batch, and — because the timing configuration is traced, not baked in —
running the same workload under many spec variants costs exactly one
engine compilation per stream-length bucket.
"""
import numpy as np

from repro.core import engine
from repro.core.pimsim import PimSimulator
from repro.core.timing import (DEFAULT_SYSTEM, LpddrTimings, PimSpec,
                               SystemSpec)
from repro.pimkernel.executor import GemvRequest, PimExecutor
from repro.pimkernel.tileconfig import PimDType

from test_engine import build_valid_stream, random_op_tuples

# A (H, W, dtype, fence, reshape) grid covering both tile groups, the
# reshape regime and the fence path.
GRID = [
    (256, 1024, PimDType.W8A8, False, False),
    (256, 1024, PimDType.W8A8, False, True),
    (512, 4096, PimDType.W8A16, True, False),
    (1024, 512, PimDType.W4A4, False, False),
    (1024, 2048, PimDType.W4A16, True, True),
    (2048, 2048, PimDType.FP_W8A8, True, False),
    (4096, 1024, PimDType.FP_W8A16, False, False),
    (4096, 4096, PimDType.W4A8, False, False),
]


def _same_result(a, b):
    assert a.cycles == b.cycles
    assert a.ns == b.ns
    assert a.flops == b.flops
    assert a.weight_bytes == b.weight_bytes
    np.testing.assert_array_equal(a.counts, b.counts)
    assert a.energy == b.energy


def test_run_many_bit_identical_to_run_gemv():
    ex = PimExecutor(DEFAULT_SYSTEM)
    reqs = [GemvRequest.pim(h, w, dt, fence=f, reshape=r)
            for (h, w, dt, f, r) in GRID]
    batched = ex.run_many(reqs)
    for req, res in zip(reqs, batched):
        solo = ex.run_gemv(req.H, req.W, req.dtype, fence=req.fence,
                           reshape=req.reshape)
        _same_result(res, solo)


def test_run_many_baseline_bit_identical():
    ex = PimExecutor(DEFAULT_SYSTEM)
    reqs = [GemvRequest.baseline(h, w, dt) for (h, w, dt, _f, _r) in GRID]
    batched = ex.run_many(reqs)
    for req, res in zip(reqs, batched):
        _same_result(res, ex.run_baseline(req.H, req.W, req.dtype))


def test_run_baseline_times_every_channel():
    """All num_channels streams flow through the engine (not 1 scaled)."""
    ex = PimExecutor(DEFAULT_SYSTEM)
    res = ex.run_baseline(1024, 1024, PimDType.W8A8)
    per_ch = res.energy["channels"]
    assert len(per_ch) == DEFAULT_SYSTEM.num_channels
    # identical replicated streams -> identical per-channel energy
    assert all(d == per_ch[0] for d in per_ch[1:])
    total = 1024 * 1024 * PimDType.W8A8.w_bits // 8
    assert res.weight_bytes == total


def test_run_many_dedupes_and_preserves_order():
    ex = PimExecutor(DEFAULT_SYSTEM)
    r1 = GemvRequest.pim(256, 1024, PimDType.W8A8)
    r2 = GemvRequest.baseline(256, 1024, PimDType.W8A8)
    res = ex.run_many([r1, r2, r1, r1, r2])
    assert res[0] is res[2] and res[0] is res[3]
    assert res[1] is res[4]
    assert res[0].meta.get("kind") != "baseline"
    assert res[1].meta.get("kind") == "baseline"


def test_simulator_cache_shared_across_query_styles():
    sim = PimSimulator()
    sw = sim.sweep([1024, 2048], [PimDType.W8A8])["W8A8"]
    # speedup() must come straight from the cache (same keys)
    assert sim.speedup(4096, 1024, PimDType.W8A8) == sw[0]
    assert sim.speedup(4096, 2048, PimDType.W8A8) == sw[1]
    direct = (sim.baseline(4096, 1024, PimDType.W8A8).ns
              / sim.gemv(4096, 1024, PimDType.W8A8).ns)
    assert direct == sw[0]


def test_multi_spec_fleet_resolves_each_spec():
    """Points with different TimingCycles share one fleet batch."""
    rng = np.random.default_rng(7)
    stream = build_valid_stream(random_op_tuples(rng))
    specs = [SystemSpec(timings=LpddrTimings(tRCD=18.0 + 2 * i))
             for i in range(4)]
    points = [(sp.derive_cycles(), [stream, stream]) for sp in specs]
    fleet = engine.resolve_fleet(points)
    totals = set()
    for sp, fr in zip(specs, fleet):
        _, solo = engine.run_streams(sp.derive_cycles(), [stream, stream])
        np.testing.assert_array_equal(solo, fr.totals)
        totals.add(int(fr.totals[0]))
    assert len(totals) > 1, "spec variants must resolve differently"


def test_one_compilation_across_spec_variants():
    """>= 8 SystemSpec variants, same workload: zero extra compiles.

    The timing configuration is traced fleet data, so the jit cache keys
    only on (num_banks, fleet bucket, length bucket) — the first variant
    pays one compilation per stream-length bucket, the rest pay none.
    """
    variants = [
        SystemSpec(timings=LpddrTimings(tRCD=16.0 + i, tRP=17.0 + i),
                   pim=PimSpec(mac_interval_ck=2 + (i % 3)),
                   fence_ns=100.0 + 10 * i)
        for i in range(8)
    ]
    cycs = [sp.derive_cycles() for sp in variants]
    assert len(set(cycs)) == 8, "variants must be distinct configs"

    rng = np.random.default_rng(3)
    streams = [build_valid_stream(random_op_tuples(rng))
               for _ in range(4)]

    engine.resolve_fleet([(cycs[0], streams)])   # compile the buckets
    warm = engine.compile_cache_size()
    totals = []
    for cyc in cycs:
        fr = engine.resolve_fleet([(cyc, streams)])[0]
        totals.append(int(fr.totals.max()))
    assert engine.compile_cache_size() == warm, \
        "spec variants must not trigger recompilation"
    assert len(set(totals)) > 1


def _timing_variants(n: int) -> list[SystemSpec]:
    """n distinct timing-only variants: identical command streams, so
    compile behavior can be asserted independently of stream content."""
    return [SystemSpec(timings=LpddrTimings(tRCD=20.0 + i, tRP=19.0 + i),
                       pim=PimSpec(mac_interval_ck=2 + (i % 3)),
                       fence_ns=120.0 + 10 * i)
            for i in range(n)]


# Acceptance grid: >= 4 shapes incl. baseline/fence/reshape coverage.
HET_SHAPES = [
    ("pim", 256, 1024, PimDType.W8A8, False, False),
    ("pim", 512, 4096, PimDType.W8A16, True, False),
    ("pim", 1024, 512, PimDType.W4A8, False, True),
    ("pim", 2048, 2048, PimDType.FP_W8A8, False, False),
    ("base", 1024, 1024, PimDType.W8A8, False, False),
]


def _het_grid(specs) -> list[GemvRequest]:
    return [GemvRequest.pim(h, w, dt, fence=f, reshape=r, spec=sp)
            if kind == "pim" else GemvRequest.baseline(h, w, dt, spec=sp)
            for sp in specs for (kind, h, w, dt, f, r) in HET_SHAPES]


def test_heterogeneous_spec_grid_one_fleet_call():
    """>= 3 SystemSpec variants x >= 4 shapes through ONE run_many,
    bit-identical to per-spec executor instances."""
    specs = [DEFAULT_SYSTEM] + _timing_variants(3)
    batched = PimExecutor().run_many(_het_grid(specs))
    it = iter(batched)
    distinct = set()
    for sp in specs:
        ex = PimExecutor(sp)
        for (kind, h, w, dt, f, r) in HET_SHAPES:
            solo = ex.run_gemv(h, w, dt, fence=f, reshape=r) \
                if kind == "pim" else ex.run_baseline(h, w, dt)
            res = next(it)
            _same_result(res, solo)
            distinct.add((sp is specs[0], res.cycles))
    # the variants genuinely time differently (not one spec replicated)
    assert len({c for _d, c in distinct}) > len(HET_SHAPES)


def test_spec_variants_do_not_grow_compile_cache():
    """compile_cache_size() is independent of the NUMBER of spec
    variants: swapping one heterogeneous variant set for another (same
    shapes, same fleet width) compiles nothing new."""
    grid_a = _het_grid(_timing_variants(4))
    grid_b = _het_grid(_timing_variants(8)[4:])
    ex = PimExecutor()
    res_a = ex.run_many(grid_a)              # pays the bucket compiles
    warm = engine.compile_cache_size()
    res_b = ex.run_many(grid_b)              # 4 brand-new specs
    assert engine.compile_cache_size() == warm, \
        "new spec variants must not trigger recompilation"
    assert {r.cycles for r in res_a} != {r.cycles for r in res_b}


def test_run_many_spec_none_resolves_to_default():
    """Spec-less requests run under the executor default and dedupe
    against explicitly-spec'd twins."""
    ex = PimExecutor(DEFAULT_SYSTEM)
    bare = GemvRequest.pim(256, 1024, PimDType.W8A8)
    explicit = GemvRequest.pim(256, 1024, PimDType.W8A8,
                               spec=DEFAULT_SYSTEM)
    res = ex.run_many([bare, explicit])
    assert res[0] is res[1]


def test_simulator_sweep_specs_grid():
    """sweep(specs=[...]) resolves the (spec x dtype x dim) surface in
    one batch and matches per-spec sweeps exactly."""
    sim = PimSimulator()
    specs = _timing_variants(3)
    surface = sim.sweep([1024, 2048], [PimDType.W8A8], specs=specs)
    assert set(surface) == {0, 1, 2}
    for i, sp in enumerate(specs):
        solo = PimSimulator(sp).sweep([1024, 2048], [PimDType.W8A8])
        assert surface[i] == solo
    vals = {tuple(surface[i]["W8A8"]) for i in surface}
    assert len(vals) == 3, "variants must produce distinct surfaces"


def test_offload_plan_grid_matches_per_spec_planners():
    from repro.configs import ARCHS
    from repro.serving.offload import OffloadPlanner
    cfg = ARCHS["mamba2-130m"]
    specs = [DEFAULT_SYSTEM] + _timing_variants(2)
    planner = OffloadPlanner(cfg)
    grid = planner.plan_grid(specs)
    assert len(grid) == len(specs)
    for sp, decisions in zip(specs, grid):
        solo = OffloadPlanner(cfg, PimSimulator(sp)).plan()
        assert [(d.site.name, d.pim_ns, d.host_ns,
                 d.offload_below_batch) for d in decisions] == \
               [(d.site.name, d.pim_ns, d.host_ns,
                 d.offload_below_batch) for d in solo]
    # cached: a repeat issues no new engine work (same objects back)
    assert planner.plan_grid(specs)[0][0] is grid[0][0]


def test_length_buckets_are_three_quarter_refined():
    """Stream lengths pad to the {2^k, 1.5 * 2^(k-1)} bucket series with
    <= 1.5x tail waste, and the refinement doesn't regress compiles."""
    assert [engine._length_bucket(n)
            for n in (1, 16, 17, 24, 25, 33, 48, 49, 64, 65)] == \
        [16, 16, 24, 24, 32, 48, 48, 64, 64, 96]
    for n in range(1, 4096):
        b = engine._length_bucket(n)
        assert b >= max(n, 16)
        assert b <= 1.5 * max(n, 11), (n, b)
        # buckets are stable: every length in [n, bucket] shares one pad
        assert engine._length_bucket(b) == b
    # two lengths inside one 3/4 bucket share a single executable
    cyc = DEFAULT_SYSTEM.derive_cycles()
    s = build_valid_stream(random_op_tuples(np.random.default_rng(11),
                                            max_ops=30))
    n = s.shape[0]
    bucket = engine._length_bucket(n)
    engine.resolve_fleet([(cyc, [s])])
    warm = engine.compile_cache_size()
    engine.resolve_fleet([(cyc, [s[: max(1, n - 2)]])])
    if engine._length_bucket(max(1, n - 2)) == bucket:
        assert engine.compile_cache_size() == warm


def test_compilations_bounded_by_length_buckets():
    """Distinct stream-length buckets compile once each; repeats reuse."""
    cyc = DEFAULT_SYSTEM.derive_cycles()
    rng = np.random.default_rng(5)
    streams = {}
    for target in (20, 200):
        while True:
            s = build_valid_stream(random_op_tuples(rng))
            if s.shape[0] and engine._length_bucket(s.shape[0]) not in \
                    streams and s.shape[0] >= target:
                streams[engine._length_bucket(s.shape[0])] = s
                break
    for s in streams.values():          # compile each bucket once
        engine.resolve_fleet([(cyc, [s])])
    warm = engine.compile_cache_size()
    for s in streams.values():          # same buckets again -> no compile
        engine.resolve_fleet([(cyc, [s])])
    assert engine.compile_cache_size() == warm


def test_slab_device_rows_count_where_slabs_ran():
    """Every resolved slab adds its padded width to the device whose
    result shards hold it — the record ``chip_smoke.py --four-chips``
    prints to show each device did its share."""
    rng = np.random.default_rng(41)
    cyc = DEFAULT_SYSTEM.derive_cycles()
    lanes = [(cyc, build_valid_stream(random_op_tuples(rng, max_ops=20)))
             for _ in range(3)]
    engine.lane_cache_reset()
    before = engine.slab_device_rows()
    engine.resolve_lanes(lanes, need_issue=False)
    after = engine.slab_device_rows()
    grown = {d: n - before.get(d, 0) for d, n in after.items()
             if n != before.get(d, 0)}
    assert set(grown) <= {str(d) for d in engine.lane_devices()}
    groups: dict[int, int] = {}
    for _, s in lanes:
        b = engine._length_bucket(s.shape[0])
        groups[b] = groups.get(b, 0) + 1
    assert sum(grown.values()) == sum(engine._fleet_bucket(n)
                                      for n in groups.values())
