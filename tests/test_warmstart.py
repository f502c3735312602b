"""Warm-start persistence: on-disk lane LRU + cache counter semantics.

The snapshot contract is replay-exactness and crash-tolerance: a saved
lane LRU loaded into a FRESH process must reproduce ``resolve_lanes``
results byte-identically with zero fleet resolves, and *no* corrupt,
truncated or mismatched snapshot may ever raise — every failure mode
degrades to a cold cache.  The ``configure_lane_cache`` counter fix
(unchanged capacity preserves hits/misses/evictions) is pinned here too.
"""
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from repro.core import engine, faults, warmstart
from repro.core.timing import DEFAULT_SYSTEM

from test_engine import build_valid_stream, random_op_tuples


def _lanes(seed: int, n: int = 5):
    rng = np.random.default_rng(seed)
    cyc = DEFAULT_SYSTEM.derive_cycles()
    return [(cyc, build_valid_stream(random_op_tuples(rng, max_ops=30)))
            for _ in range(n)]


def _keys(n: int = 5):
    return [("warm", i) for i in range(n)]


@pytest.fixture(autouse=True)
def _fresh_lane_cache():
    engine.lane_cache_reset()
    yield
    engine.lane_cache_reset()


# ---------------------------------------------------------------------
# Round trip: save -> (fresh state) -> load -> replay with zero resolves
# ---------------------------------------------------------------------

def test_snapshot_round_trip_zero_misses(tmp_path):
    lanes = _lanes(0)
    ref = engine.resolve_lanes(lanes, keys=_keys(), need_issue=False)
    assert warmstart.save_lane_snapshot(str(tmp_path)) == 5

    engine.lane_cache_reset()                 # simulate a fresh process
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 5
    info = engine.lane_cache_info()
    assert info["misses"] == 0 and info["hits"] == 0   # import is silent

    got = engine.resolve_lanes(lanes, keys=_keys(), need_issue=False)
    info = engine.lane_cache_info()
    assert info["misses"] == 0, "warm replay must not resolve"
    assert info["hits"] == 5
    assert [t for _, t in ref] == [t for _, t in got]


def test_snapshot_round_trip_fresh_process(tmp_path):
    """The real thing: a separate interpreter loads the snapshot and
    replays byte-identically with zero fleet resolves."""
    lanes = _lanes(7)
    ref = engine.resolve_lanes(lanes, keys=_keys(), need_issue=False)
    warmstart.save_lane_snapshot(str(tmp_path))

    child = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from repro.core import engine, warmstart\n"
        "from repro.core.timing import DEFAULT_SYSTEM\n"
        "from test_engine import build_valid_stream, random_op_tuples\n"
        "warmstart.load_lane_snapshot(sys.argv[1])\n"
        "rng = np.random.default_rng(7)\n"
        "cyc = DEFAULT_SYSTEM.derive_cycles()\n"
        "lanes = [(cyc, build_valid_stream(random_op_tuples(rng,"
        " max_ops=30))) for _ in range(5)]\n"
        "keys = [('warm', i) for i in range(5)]\n"
        "res = engine.resolve_lanes(lanes, keys=keys, need_issue=False)\n"
        "info = engine.lane_cache_info()\n"
        "print(json.dumps(dict(totals=[int(t) for _, t in res],"
        " misses=info['misses'])))\n"
    ) % os.path.dirname(os.path.abspath(__file__))
    # Propagate this interpreter's import roots: the child must find
    # `repro` even when the repo runs from a src-layout checkout without
    # a pip install (pytest injects src/ via pyproject pythonpath, which
    # subprocesses do not inherit).
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", child, str(tmp_path)],
                         capture_output=True, text=True, check=True,
                         env=env)
    import json
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["misses"] == 0
    assert rep["totals"] == [int(t) for _, t in ref]


def test_snapshot_save_is_atomic_and_empty_cache_saves(tmp_path):
    assert warmstart.save_lane_snapshot(str(tmp_path)) == 0
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 0
    leftovers = [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert not leftovers, f"tmp files left behind: {leftovers}"


# ---------------------------------------------------------------------
# Corruption / version tolerance: cold start, never a crash
# ---------------------------------------------------------------------

def _saved_snapshot(tmp_path):
    engine.resolve_lanes(_lanes(1), keys=_keys(), need_issue=False)
    warmstart.save_lane_snapshot(str(tmp_path))
    return warmstart.lane_snapshot_path(str(tmp_path))


def test_missing_snapshot_is_cold(tmp_path):
    assert warmstart.load_lane_snapshot(str(tmp_path / "nowhere")) == 0


def test_truncated_snapshot_is_cold(tmp_path):
    path = _saved_snapshot(tmp_path)
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])
    engine.lane_cache_reset()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 0
    assert engine.lane_cache_info()["size"] == 0


def test_garbage_snapshot_is_cold(tmp_path):
    path = _saved_snapshot(tmp_path)
    with open(path, "wb") as f:
        f.write(b"not a pickle at all")
    engine.lane_cache_reset()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 0


def test_version_mismatch_is_cold(tmp_path):
    path = _saved_snapshot(tmp_path)
    payload = pickle.load(open(path, "rb"))
    payload["version"] = warmstart.SNAPSHOT_VERSION + 1
    with open(path, "wb") as f:
        pickle.dump(payload, f)
    engine.lane_cache_reset()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 0


def test_fingerprint_mismatch_is_cold(tmp_path):
    path = _saved_snapshot(tmp_path)
    payload = pickle.load(open(path, "rb"))
    payload["fingerprint"] = "0" * 32
    with open(path, "wb") as f:
        pickle.dump(payload, f)
    engine.lane_cache_reset()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 0


def test_malformed_payload_shapes_are_cold(tmp_path):
    path = warmstart.lane_snapshot_path(str(tmp_path))
    os.makedirs(tmp_path, exist_ok=True)
    for payload in (["a", "list"], {"magic": b"wrong"},
                    {"magic": warmstart._MAGIC,
                     "version": warmstart.SNAPSHOT_VERSION,
                     "fingerprint": warmstart.snapshot_fingerprint(),
                     "entries": "not-a-list"}):
        with open(path, "wb") as f:
            pickle.dump(payload, f)
        assert warmstart.load_lane_snapshot(str(tmp_path)) == 0


# ---------------------------------------------------------------------
# Crash-mid-write: the previous snapshot must survive a dying writer
# ---------------------------------------------------------------------

def test_injected_crash_mid_write_preserves_previous_snapshot(tmp_path):
    """A writer that dies after fsync but before the atomic rename (the
    armed ``warmstart`` seam) leaves the previous snapshot intact and no
    tmp litter behind."""
    engine.resolve_lanes(_lanes(8), keys=_keys(), need_issue=False)
    assert warmstart.save_lane_snapshot(str(tmp_path)) == 5

    engine.resolve_lanes(_lanes(9), keys=[("v2", i) for i in range(5)],
                         need_issue=False)
    inj = faults.FaultInjector()
    inj.arm("warmstart", count=1, message="crash mid-write")
    with faults.fault_scope(inj):
        with pytest.raises(faults.InjectedFault):
            warmstart.save_lane_snapshot(str(tmp_path))

    leftovers = [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert not leftovers, f"tmp files left behind: {leftovers}"
    engine.lane_cache_reset()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 5  # v1 intact


def test_hard_kill_mid_write_preserves_previous_snapshot(tmp_path):
    """The real thing: a separate interpreter is hard-killed (os._exit)
    at the injection seam — after the tmp file is written and fsynced,
    before ``os.replace``.  The parent must still load the previous
    snapshot; a leftover tmp file is acceptable crash litter but must
    never shadow the real snapshot."""
    engine.resolve_lanes(_lanes(10), keys=_keys(), need_issue=False)
    assert warmstart.save_lane_snapshot(str(tmp_path)) == 5
    ref = engine.lane_cache_export()

    child = (
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from repro.core import engine, faults, warmstart\n"
        "from repro.core.timing import DEFAULT_SYSTEM\n"
        "from test_engine import build_valid_stream, random_op_tuples\n"
        "rng = np.random.default_rng(11)\n"
        "cyc = DEFAULT_SYSTEM.derive_cycles()\n"
        "lanes = [(cyc, build_valid_stream(random_op_tuples(rng,"
        " max_ops=30))) for _ in range(3)]\n"
        "engine.resolve_lanes(lanes, keys=[('kill', i) for i in range(3)],"
        " need_issue=False)\n"
        "faults.maybe_fail = lambda site: os._exit(9)\n"
        "warmstart.faults.maybe_fail = faults.maybe_fail\n"
        "warmstart.save_lane_snapshot(sys.argv[1])\n"
    ) % os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", child, str(tmp_path)],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 9, out.stderr

    engine.lane_cache_reset()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 5
    assert engine.lane_cache_export() == ref


def test_save_warm_start_absorbs_failure(tmp_path, monkeypatch):
    """A failing save is advisory: ``save_warm_start`` returns -1 and
    records a structured ``fault`` event instead of raising."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    inj = faults.FaultInjector()
    inj.arm("warmstart", count=1)
    with faults.fault_scope(inj):
        assert warmstart.save_warm_start() == -1
    evs = [e for e in faults.events()
           if e["site"] == "warmstart" and e["kind"] == "fault"]
    assert evs and "snapshot save failed" in evs[0]["detail"]


def test_rejected_snapshot_records_detect_event(tmp_path):
    path = _saved_snapshot(tmp_path)
    with open(path, "wb") as f:
        f.write(b"garbage, not a pickle")
    engine.lane_cache_reset()
    faults.reset_events()
    assert warmstart.load_lane_snapshot(str(tmp_path)) == 0
    evs = [e for e in faults.events()
           if e["site"] == "warmstart" and e["kind"] == "detect"]
    assert evs and "cold start" in evs[0]["detail"]


# ---------------------------------------------------------------------
# enable/save_warm_start wiring + env knob
# ---------------------------------------------------------------------

def test_enable_warm_start_no_dir_is_noop(monkeypatch):
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    rep = warmstart.enable_warm_start()
    assert rep == {"cache_dir": None, "lanes": 0}
    assert warmstart.save_warm_start() == -1


def test_env_cache_dir_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    engine.resolve_lanes(_lanes(2), keys=_keys(), need_issue=False)
    assert warmstart.save_warm_start() == 5
    engine.lane_cache_reset()
    rep = warmstart.enable_warm_start()
    assert rep["cache_dir"] == str(tmp_path) and rep["lanes"] == 5


def test_import_respects_capacity(tmp_path):
    engine.resolve_lanes(_lanes(3), keys=_keys(), need_issue=False)
    warmstart.save_lane_snapshot(str(tmp_path))
    engine.configure_lane_cache(2)            # shrink (clears)
    try:
        kept = warmstart.load_lane_snapshot(str(tmp_path))
        assert kept == 2                      # newest 2 survive
        info = engine.lane_cache_info()
        assert info["size"] == 2 and info["evictions"] == 0
    finally:
        engine.configure_lane_cache(4096)


def test_import_disabled_cache_keeps_nothing(tmp_path):
    engine.resolve_lanes(_lanes(4), keys=_keys(), need_issue=False)
    warmstart.save_lane_snapshot(str(tmp_path))
    engine.configure_lane_cache(0)
    try:
        assert warmstart.load_lane_snapshot(str(tmp_path)) == 0
    finally:
        engine.configure_lane_cache(4096)


# ---------------------------------------------------------------------
# XLA compile cache: placed from outside, else one fixed in-checkout path
# ---------------------------------------------------------------------

@pytest.fixture
def _restore_compile_cache_config():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield jax.config
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_compile_cache_honours_env_dir(tmp_path, monkeypatch,
                                       _restore_compile_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache
    and the code sets no directory of its own."""
    config = _restore_compile_cache_config
    before = config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    assert warmstart.enable_compilation_cache() == str(tmp_path / "xla")
    assert config.jax_compilation_cache_dir == before
    assert not (tmp_path / "xla").exists()      # nothing created by us


def test_compile_cache_fixed_checkout_path_when_unset(
        monkeypatch, _restore_compile_cache_config):
    """Unset, the cache sits at one fixed path inside the checkout — the
    same path every call and every process, never a temp directory and
    never tied to ``--cache-dir``."""
    config = _restore_compile_cache_config
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("REPRO_CACHE_DIR", "/nonexistent-lane-dir")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert warmstart.enable_compilation_cache() == want
    assert config.jax_compilation_cache_dir == want
    assert config.jax_persistent_cache_min_compile_time_secs == 0
    assert warmstart.enable_compilation_cache() == want   # stable


# ---------------------------------------------------------------------
# configure_lane_cache counter semantics (satellite fix)
# ---------------------------------------------------------------------

def test_reconfigure_same_capacity_preserves_state():
    lanes = _lanes(5)
    engine.resolve_lanes(lanes, keys=_keys(), need_issue=False)
    engine.resolve_lanes(lanes, keys=_keys(), need_issue=False)
    before = engine.lane_cache_info()
    assert before["hits"] == 5 and before["misses"] == 5

    engine.configure_lane_cache(before["maxsize"])   # unchanged: no-op
    assert engine.lane_cache_info() == before

    engine.resolve_lanes(lanes, keys=_keys(), need_issue=False)
    assert engine.lane_cache_info()["hits"] == 10    # entries survived


def test_reconfigure_new_capacity_still_clears():
    engine.resolve_lanes(_lanes(6), keys=_keys(), need_issue=False)
    engine.configure_lane_cache(1024)                # change: clears
    try:
        info = engine.lane_cache_info()
        assert info == dict(size=0, maxsize=1024, hits=0, misses=0,
                            evictions=0)
    finally:
        engine.configure_lane_cache(4096)
