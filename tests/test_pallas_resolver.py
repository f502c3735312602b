"""Differential suite for the Pallas lane-resolver backend.

The Pallas kernel (``kernels/lane_scan.py``) is the fourth lane backend
(single-device scan, threaded multi-device, ``shard_map`` mesh, Pallas).
Its contract is bit-identity with the scan resolver — and therefore with
``RefEngine`` — on every lane, plus clean selection semantics:
``configure_lane_backend``/``REPRO_LANE_BACKEND`` pick it, ``auto``
follows the platform rule, an explicit ``pallas`` request that cannot be
built raises instead of running another backend, and the engine's
dedupe/LRU/slab plumbing is backend-oblivious.  The scan-unroll
satellite lives here too: unroll={1,2,4,8} must be bit-identical.
"""
import numpy as np
import pytest

from repro.core import engine, faults
from repro.core.engine_ref import RefEngine
from repro.core.timing import DEFAULT_SYSTEM

from test_conformance import assert_fleet_matches_ref, fleet_from_seed
from test_engine import build_valid_stream, random_op_tuples

from repro.kernels import lane_scan

# The platform rule (no kernel is built while the module is imported).
PALLAS_OK = lane_scan.pallas_lane_supported()
needs_pallas = pytest.mark.skipif(
    not PALLAS_OK, reason="pallas lane resolver unsupported here")


def _lanes(seed: int, n: int = 6, max_ops: int = 40):
    rng = np.random.default_rng(seed)
    cyc = DEFAULT_SYSTEM.derive_cycles()
    return [(cyc, build_valid_stream(random_op_tuples(rng,
                                                      max_ops=max_ops)))
            for _ in range(n)]


# ---------------------------------------------------------------------
# Bit-identity: pallas vs scan vs RefEngine
# ---------------------------------------------------------------------

@needs_pallas
@pytest.mark.parametrize("seed", range(4))
def test_pallas_bit_identical_to_scan(seed):
    lanes = _lanes(seed)
    engine.lane_cache_reset()
    ref = engine.resolve_lanes(lanes)
    engine.lane_cache_reset()
    with engine.lane_backend_scope("pallas"):
        got = engine.resolve_lanes(lanes)
    for (iss_a, tot_a), (iss_b, tot_b) in zip(ref, got):
        assert tot_a == tot_b
        np.testing.assert_array_equal(iss_a, iss_b)


@needs_pallas
def test_pallas_multi_spec_fleet_matches_ref():
    """The conformance corpus (mixed bank counts, fuzzed timings)
    straight through the Pallas backend against the Python oracle."""
    with engine.lane_backend_scope("pallas"):
        assert_fleet_matches_ref(fleet_from_seed(17))


@needs_pallas
def test_pallas_resolver_direct_matches_ref():
    """The raw ``make_lane_resolver`` output (no engine plumbing) against
    ``RefEngine`` on a hand-rolled fleet batch."""
    cyc = DEFAULT_SYSTEM.derive_cycles()
    rng = np.random.default_rng(3)
    streams = [build_valid_stream(random_op_tuples(rng, max_ops=24))
               for _ in range(3)]
    n = max(s.shape[0] for s in streams)
    batch = np.zeros((len(streams), 4, n), dtype=np.int32)
    for i, s in enumerate(streams):
        batch[i, :, : s.shape[0]] = s.T
    cycs = engine.stack_cycles([cyc] * len(streams))
    issue, total = lane_scan.make_lane_resolver(cyc.num_banks)(cycs, batch)
    ref = RefEngine(cyc, validate=False)
    for i, s in enumerate(streams):
        iss_ref, tot_ref = ref.run(s)
        np.testing.assert_array_equal(
            iss_ref, np.asarray(issue)[i, : s.shape[0]].astype(np.int64))
        assert tot_ref == int(total[i])


# ---------------------------------------------------------------------
# Selection semantics: config > env > default; no hidden fallback
# ---------------------------------------------------------------------

def test_backend_config_precedence(monkeypatch):
    # Env-neutral: the pallas CI job exports REPRO_LANE_BACKEND.
    monkeypatch.delenv("REPRO_LANE_BACKEND", raising=False)
    assert engine.lane_backend() == "scan"      # default
    monkeypatch.setenv("REPRO_LANE_BACKEND", "pallas")
    assert engine.lane_backend() == "pallas"
    engine.configure_lane_backend("scan")       # config wins over env
    assert engine.lane_backend() == "scan"
    engine.configure_lane_backend(None)
    assert engine.lane_backend() == "pallas"


def test_backend_invalid_names_rejected(monkeypatch):
    with pytest.raises(ValueError):
        engine.configure_lane_backend("cuda")
    monkeypatch.setenv("REPRO_LANE_BACKEND", "nonsense")
    assert engine.lane_backend() == "scan"   # invalid env value ignored


def test_backend_scope_restores_on_error(monkeypatch):
    monkeypatch.delenv("REPRO_LANE_BACKEND", raising=False)
    with pytest.raises(RuntimeError):
        with engine.lane_backend_scope("pallas"):
            raise RuntimeError("boom")
    assert engine.lane_backend() == "scan"


def test_pallas_falls_back_to_scan_when_unsupported(monkeypatch):
    """It no longer does: where the kernel cannot be built, an explicit
    pallas request raises the compiler's reason — from the backend query
    and from resolution alike — and never runs the scan path instead,
    while ``auto`` follows the platform rule to scan."""
    def refuse(num_banks, unroll=None, interpret=None):
        raise NotImplementedError("Mosaic: no lowering for scan")

    lane_scan.require_lane_kernel.cache_clear()
    monkeypatch.setattr(lane_scan, "make_lane_resolver", refuse)
    monkeypatch.setattr(lane_scan, "pallas_lane_supported", lambda: False)
    lanes = _lanes(0, n=2, max_ops=12)
    try:
        with engine.lane_backend_scope("pallas"):
            with pytest.raises(NotImplementedError, match="Mosaic"):
                engine.resolved_lane_backend()
            engine.lane_cache_reset()
            with pytest.raises(NotImplementedError, match="Mosaic"):
                engine.resolve_lanes(lanes)
        assert not [e for e in faults.events() if e["kind"] == "degrade"]
        with engine.lane_backend_scope("auto"):
            assert engine.resolved_lane_backend() == "scan"
            assert len(engine.resolve_lanes(lanes)) == 2
    finally:
        lane_scan.require_lane_kernel.cache_clear()


@needs_pallas
def test_auto_backend_selects_pallas_when_supported():
    with engine.lane_backend_scope("auto"):
        assert engine.resolved_lane_backend() == "pallas"


@needs_pallas
def test_pallas_backend_shares_lane_cache():
    """Dedupe/LRU is backend-oblivious: a lane resolved under scan is a
    cache hit under pallas (same key space, bit-identical values)."""
    lanes = _lanes(11, n=3)
    keys = [("pallas-share", i) for i in range(3)]
    engine.lane_cache_reset()
    engine.resolve_lanes(lanes, keys=keys, need_issue=False)
    before = engine.lane_cache_info()
    with engine.lane_backend_scope("pallas"):
        engine.resolve_lanes(lanes, keys=keys, need_issue=False)
    after = engine.lane_cache_info()
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 3


# ---------------------------------------------------------------------
# Scan-unroll satellite: env-configurable, bit-identical across values
# ---------------------------------------------------------------------

def test_scan_unroll_default_and_env(monkeypatch):
    assert engine.scan_unroll() == 4
    monkeypatch.setenv("REPRO_SCAN_UNROLL", "2")
    assert engine.scan_unroll() == 2
    assert engine.configure_scan_unroll(8) == 8   # config wins over env
    engine.configure_scan_unroll(None)
    assert engine.scan_unroll() == 2
    with pytest.raises(ValueError):
        engine.configure_scan_unroll(0)


@pytest.mark.parametrize("unroll", [1, 2, 4, 8])
def test_scan_unroll_bit_identical(unroll):
    lanes = _lanes(23, n=4)
    engine.lane_cache_reset()
    baseline = engine.resolve_lanes(lanes)
    engine.configure_scan_unroll(unroll)
    engine.lane_cache_reset()
    got = engine.resolve_lanes(lanes)
    for (iss_a, tot_a), (iss_b, tot_b) in zip(baseline, got):
        assert tot_a == tot_b
        np.testing.assert_array_equal(iss_a, iss_b)


@needs_pallas
@pytest.mark.parametrize("unroll", [1, 8])
def test_pallas_unroll_bit_identical(unroll):
    """The kernel body honours the unroll knob too — same lanes out."""
    lanes = _lanes(29, n=3)
    engine.lane_cache_reset()
    baseline = engine.resolve_lanes(lanes)
    engine.configure_scan_unroll(unroll)
    with engine.lane_backend_scope("pallas"):
        engine.lane_cache_reset()
        got = engine.resolve_lanes(lanes)
    for (iss_a, tot_a), (iss_b, tot_b) in zip(baseline, got):
        assert tot_a == tot_b
        np.testing.assert_array_equal(iss_a, iss_b)


def test_unroll_keys_separate_compile_cache_entries():
    """Distinct unroll values are distinct resolver cache keys — no
    silent reuse of a mismatched compilation."""
    lanes = _lanes(31, n=2, max_ops=16)
    nb = DEFAULT_SYSTEM.derive_cycles().num_banks
    # Pin the scan backend: under REPRO_LANE_BACKEND=pallas the resolves
    # would route through the pallas kernel and never key _RESOLVERS.
    with engine.lane_backend_scope("scan"):
        engine.configure_scan_unroll(1)
        engine.lane_cache_reset()
        engine.resolve_lanes(lanes)
        engine.configure_scan_unroll(2)
        engine.lane_cache_reset()
        engine.resolve_lanes(lanes)
    assert {(nb, 1), (nb, 2)} <= set(engine._RESOLVERS)
