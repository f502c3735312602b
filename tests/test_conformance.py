"""Differential conformance suite: fleet engine vs the Python oracle.

PIMSIM-NN-style reference-model conformance for the spec-vectorized
facade: fuzzed *multi-spec* fleets — points varying bank counts, JEDEC
timings, PIM knobs and stream lengths — resolve through ONE batched
``engine.resolve_fleet`` call and every lane must match ``RefEngine``
cycle-exactly.  The same discipline is applied one layer up
(``PimExecutor.run_many`` over heterogeneous ``SystemSpec``s, and the
batched functional path), and a committed golden fixture pins the
cycle/energy outputs of a small (spec x shape) grid so facade refactors
cannot silently drift.

When hypothesis is unavailable the fuzz tests fall back to a
deterministic seeded corpus (CI runs both flavors).
"""
import json
import pathlib

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:        # collection must never hard-fail
    HAVE_HYPOTHESIS = False

from repro.core import engine
from repro.core.engine_ref import RefEngine
from repro.core.timing import (DEFAULT_SYSTEM, LpddrTimings, PimSpec,
                               SystemSpec)
from repro.pimkernel.executor import (FunctionalGemv, GemvRequest,
                                      PimExecutor)
from repro.pimkernel.grids import golden_record, golden_requests
from repro.pimkernel.tileconfig import PimDType

from test_engine import build_valid_stream, random_op_tuples

GOLDEN = pathlib.Path(__file__).parent / "golden" / "fleet_parity.json"


# ---------------------------------------------------------------------
# Spec + fleet generators (shared by hypothesis and the fallback corpus)
# ---------------------------------------------------------------------

def make_spec(bankgroups: int, t_rcd: float, t_rp: float, t_ras: float,
              mac_i: int, srf_i: int, fence_ns: float) -> SystemSpec:
    """One fuzzed design point (num_banks = 4 * bankgroups)."""
    return SystemSpec(
        timings=LpddrTimings(num_bankgroups=bankgroups, tRCD=t_rcd,
                             tRP=t_rp, tRAS=t_ras),
        pim=PimSpec(mac_interval_ck=mac_i, srf_wr_interval_ck=srf_i),
        fence_ns=fence_ns)


def clamp_banks(ops, nb: int):
    """Restrict op-tuple bank ids to the spec's bank count."""
    return [(kind, bank % nb, row, n) for (kind, bank, row, n) in ops]


def fleet_from_seed(seed: int, n_points: int = 4):
    """Deterministic multi-spec fleet: (spec, streams) points."""
    rng = np.random.default_rng(seed)
    points = []
    for _ in range(n_points):
        spec = make_spec(
            bankgroups=int(rng.integers(2, 5)),
            t_rcd=float(rng.integers(12, 31)),
            t_rp=float(rng.integers(12, 31)),
            t_ras=float(rng.integers(30, 55)),
            mac_i=int(rng.integers(1, 7)),
            srf_i=int(rng.integers(8, 21)),
            fence_ns=float(rng.integers(50, 301)))
        nb = spec.timings.num_banks
        n_ch = int(rng.integers(1, 4))
        streams = [build_valid_stream(
            clamp_banks(random_op_tuples(rng, max_ops=30), nb))
            for _ in range(n_ch)]
        points.append((spec, streams))
    return points


def assert_fleet_matches_ref(points):
    """One resolve_fleet dispatch; every lane checked against RefEngine."""
    fleet = engine.resolve_fleet(
        [(spec.derive_cycles(), streams) for spec, streams in points])
    for (spec, streams), fr in zip(points, fleet):
        ref = RefEngine(spec.derive_cycles(), validate=False)
        for ci, s in enumerate(streams):
            iss_ref, tot_ref = ref.run(s)
            np.testing.assert_array_equal(
                iss_ref, fr.issue[ci].astype(np.int64),
                err_msg=f"issue divergence: spec={spec}, lane={ci}")
            assert tot_ref == int(fr.totals[ci]), \
                f"total divergence: spec={spec}, lane={ci}"


# ---------------------------------------------------------------------
# Fuzzed multi-spec fleets (engine layer)
# ---------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    def _point_strategy():
        spec = st.builds(
            make_spec,
            bankgroups=st.integers(2, 4),
            t_rcd=st.integers(12, 30).map(float),
            t_rp=st.integers(12, 30).map(float),
            t_ras=st.integers(30, 54).map(float),
            mac_i=st.integers(1, 6),
            srf_i=st.integers(8, 20),
            fence_ns=st.integers(50, 300).map(float))
        ops = st.lists(st.tuples(st.integers(0, 2), st.integers(0, 15),
                                 st.integers(0, 127), st.integers(0, 30)),
                       min_size=1, max_size=30)
        return spec.flatmap(lambda sp: st.tuples(
            st.just(sp),
            st.lists(ops.map(lambda o: build_valid_stream(
                clamp_banks(o, sp.timings.num_banks))),
                min_size=1, max_size=3)))

    @settings(max_examples=12, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(st.lists(_point_strategy(), min_size=1, max_size=4))
    def test_fuzzed_multi_spec_fleet_matches_ref(points):
        assert_fleet_matches_ref(points)
else:                      # deterministic fallback when hypothesis absent
    @pytest.mark.parametrize("seed", range(10))
    def test_fuzzed_multi_spec_fleet_matches_ref(seed):
        assert_fleet_matches_ref(fleet_from_seed(seed))


# ---------------------------------------------------------------------
# Backend battery: the SAME conformance corpus through every selectable
# lane backend — the Pallas resolver must match RefEngine lane-for-lane
# exactly like the scan path (bit-identity is the backend contract).
# ---------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["scan", "pallas"])
@pytest.mark.parametrize("seed", range(3))
def test_backend_fleet_matches_ref(backend, seed):
    if backend == "pallas":
        from repro.kernels import lane_scan
        if not lane_scan.pallas_lane_supported():
            pytest.skip("pallas lane resolver unsupported here")
    with engine.lane_backend_scope(backend):
        assert engine.resolved_lane_backend() == backend
        assert_fleet_matches_ref(fleet_from_seed(seed))


def test_mixed_bank_counts_share_one_dispatch():
    """8/12/16-bank design points resolve correctly in one fleet batch
    (one resolver per bank count, grouped under the hood)."""
    points = []
    rng = np.random.default_rng(99)
    for bg in (2, 3, 4, 2, 4):
        spec = make_spec(bg, 18.0, 18.0, 42.0, 3, 14, 150.0)
        nb = spec.timings.num_banks
        points.append((spec, [build_valid_stream(
            clamp_banks(random_op_tuples(rng, max_ops=25), nb))]))
    assert_fleet_matches_ref(points)


# ---------------------------------------------------------------------
# Facade layer: heterogeneous run_many lanes vs RefEngine
# ---------------------------------------------------------------------

FACADE_SPECS = [
    DEFAULT_SYSTEM,
    SystemSpec(timings=LpddrTimings(tRCD=24.0, tRP=22.0),
               pim=PimSpec(mac_interval_ck=2)),
    SystemSpec(timings=LpddrTimings(num_bankgroups=2, tRAS=48.0),
               fence_ns=250.0),
]
FACADE_SHAPES = [(64, 512, PimDType.W8A8, False, False),
                 (128, 256, PimDType.W8A16, True, False),
                 (130, 512, PimDType.W4A8, False, True),
                 (64, 1024, PimDType.W8A8, False, False)]


def test_facade_multi_spec_lanes_match_ref():
    """Every lane of a heterogeneous run_many fleet — built streams
    under 3 spec variants x 4 shapes — matches RefEngine cycle-exactly,
    including the reported max-channel cycle count."""
    ex = PimExecutor()
    reqs = [GemvRequest.pim(h, w, dt, fence=f, reshape=r, spec=sp)
            for sp in FACADE_SPECS
            for (h, w, dt, f, r) in FACADE_SHAPES]
    results = ex.run_many(reqs)
    planned = ex.plan_many(reqs)
    for p, res in zip(planned, results):
        ref = RefEngine(p.ctx.cyc, validate=False)
        ref_totals = [ref.run(s)[1] for s in p.streams]
        assert res.cycles == max(ref_totals), \
            f"facade/ref divergence for {p.req}"


def test_functional_batch_multi_spec():
    """Batched HW/SW co-simulation: one timing dispatch, every lane
    correct — y must equal W @ x for every item, across heterogeneous
    specs, and the batch must be bit-identical to the one-item path."""
    rng = np.random.default_rng(5)
    items = []
    for spec in (DEFAULT_SYSTEM, FACADE_SPECS[1]):
        for (h, w) in ((64, 512), (96, 700)):
            wts = rng.integers(-128, 128, size=(h, w)).astype(np.int32)
            x = rng.integers(-128, 128, size=(w,)).astype(np.int32)
            items.append(FunctionalGemv(wts, x, PimDType.W8A8, spec=spec))
    ex = PimExecutor()
    batched = ex.run_functional_many(items)
    for it, (y, res) in zip(items, batched):
        np.testing.assert_array_equal(
            y, it.weights.astype(np.int64) @ it.x.astype(np.int64))
        y1, res1 = ex.run_gemv_functional(it.weights, it.x, it.dtype,
                                          spec=it.spec)
        np.testing.assert_array_equal(y, y1)
        assert res.cycles == res1.cycles and res.energy == res1.energy


# ---------------------------------------------------------------------
# Golden parity: committed fixtures pin the PR-1 cycle/energy numbers
# ---------------------------------------------------------------------

def _snapshot():
    labels, reqs = zip(*golden_requests())
    results = PimExecutor().run_many(list(reqs))
    return {label: golden_record(res)
            for label, res in zip(labels, results)}


def test_golden_parity_exact():
    """Cycle/energy outputs for the fixed (spec x shape) grid are diffed
    EXACTLY against the committed fixture — any drift is a regression
    (regenerate deliberately with `python tests/test_conformance.py`)."""
    fixture = json.loads(GOLDEN.read_text())
    # JSON round-trip normalizes float repr on both sides of the diff.
    current = json.loads(json.dumps(_snapshot()))
    assert set(current) == set(fixture)
    for label in fixture:
        assert current[label] == fixture[label], \
            f"golden drift at {label}"


if __name__ == "__main__":          # regenerate the committed fixture
    GOLDEN.parent.mkdir(parents=True, exist_ok=True)
    GOLDEN.write_text(json.dumps(_snapshot(), indent=1, sort_keys=True))
    print(f"wrote {GOLDEN}")
