"""Persistent warm-start caches: XLA compile cache + on-disk lane LRU.

A fresh simulator process pays two cold-start costs before it reaches
steady-state throughput: XLA recompilation of the fleet resolvers
(seconds per (num_banks, width-bucket, length-bucket) triple) and a cold
resolved-lane LRU (every structural stream key re-resolved once).  Both
are pure caches of deterministic computations, so both persist:

* :func:`enable_compilation_cache` turns on JAX's persistent compilation
  cache.  Its directory comes from outside: ``JAX_COMPILATION_CACHE_DIR``
  when set (JAX reads it itself; no other directory is set in code),
  else one fixed path inside the checkout, ``<repo>/.jax_cache``.  The
  path is part of the cache key, so it never moves.
* :func:`save_lane_snapshot` / :func:`load_lane_snapshot` round-trip the
  engine's resolved-lane LRU (``engine.lane_cache_export`` /
  ``lane_cache_import``) through a versioned, fingerprinted pickle at
  ``<cache_dir>/lanes.pkl``, so a fresh serve process replays cached
  lanes with *zero* fleet resolves.

The snapshot is advisory, never load-bearing: the fingerprint (blake2b
over the snapshot format version, the opcode table, and the
``TimingCycles`` field layout) rejects snapshots written by a different
engine revision, and *any* failure to read — truncated file, corrupt
pickle, wrong version, wrong fingerprint — degrades to a cold cache
instead of raising.  Writes are atomic (tmp + ``os.replace``) so a
crashed writer can at worst leave the previous snapshot in place.

The launchers (``launch/serve.py`` / ``dryrun.py`` / ``train.py``) call
:func:`enable_compilation_cache` at startup; ``serve`` and ``dryrun``
wire the lane snapshot behind ``--cache-dir`` / ``REPRO_CACHE_DIR`` via
:func:`enable_warm_start` at startup and :func:`save_warm_start` at exit.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import tempfile

from . import commands as C
from . import engine
from . import faults
from .timing import TimingCycles

SNAPSHOT_VERSION = 1
_MAGIC = b"repro-lane-snapshot"

_ENV_CACHE_DIR = "REPRO_CACHE_DIR"


def cache_dir_from_env() -> str | None:
    """The ``REPRO_CACHE_DIR`` env knob (None when unset/empty)."""
    d = os.environ.get(_ENV_CACHE_DIR, "").strip()
    return d or None


def snapshot_fingerprint() -> str:
    """Engine-revision fingerprint a snapshot must match to load.

    Hashes the things a cached ``(key -> total, issue)`` mapping is only
    valid under: the snapshot format version, the opcode table (names and
    count — renumbering opcodes silently changes stream semantics), and
    the ``TimingCycles`` field layout (keys embed ``TimingCycles``
    instances; a field added or reordered means old totals no longer
    describe the same timing model).
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(_MAGIC)
    h.update(str(SNAPSHOT_VERSION).encode())
    h.update((",".join(C.OP_NAMES) + f":{C.NUM_OPCODES}").encode())
    h.update(",".join(
        f.name for f in dataclasses.fields(TimingCycles)).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Lane-LRU snapshot
# ---------------------------------------------------------------------------

def lane_snapshot_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, "lanes.pkl")


def save_lane_snapshot(cache_dir: str) -> int:
    """Atomically write the current lane LRU under ``cache_dir``.

    Returns the number of entries written.  An empty cache still writes a
    (valid, empty) snapshot — "warm but empty" and "never saved" are
    different states to a replay harness.
    """
    os.makedirs(cache_dir, exist_ok=True)
    entries = engine.lane_cache_export()
    payload = {
        "magic": _MAGIC,
        "version": SNAPSHOT_VERSION,
        "fingerprint": snapshot_fingerprint(),
        "entries": entries,
    }
    path = lane_snapshot_path(cache_dir)
    fd, tmp = tempfile.mkstemp(dir=cache_dir, prefix=".lanes-", suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
            f.flush()
            os.fsync(f.fileno())
        faults.maybe_fail("warmstart")   # crash-mid-write injection seam
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return len(entries)


def load_lane_snapshot(cache_dir: str) -> int:
    """Load a lane snapshot into the engine's LRU; returns entries kept.

    Corruption-tolerant by contract: any failure mode — missing file,
    truncation, un-unpicklable bytes, version or fingerprint mismatch,
    malformed entries — returns 0 and leaves the cache cold.  Never
    raises.
    """
    path = lane_snapshot_path(cache_dir)
    if not os.path.exists(path):
        return 0
    try:
        faults.maybe_fail("warmstart")   # corrupt-read injection seam
        with open(path, "rb") as f:
            payload = pickle.load(f)
        reason = _reject_reason(payload)
        if reason is None:
            return engine.lane_cache_import(payload["entries"])
    except Exception as e:  # noqa: BLE001 - cold start beats a crash
        reason = f"{type(e).__name__}: {e}"
    faults.record_event("warmstart", "detect",
                        f"snapshot rejected, cold start: {reason}")
    return 0


def _reject_reason(payload) -> str | None:
    """Why a decoded snapshot payload is unusable (None = valid)."""
    if not isinstance(payload, dict):
        return f"payload is {type(payload).__name__}, not dict"
    if payload.get("magic") != _MAGIC:
        return "bad magic"
    if payload.get("version") != SNAPSHOT_VERSION:
        return f"version {payload.get('version')!r} != {SNAPSHOT_VERSION}"
    if payload.get("fingerprint") != snapshot_fingerprint():
        return "engine fingerprint mismatch"
    if not isinstance(payload.get("entries"), list):
        return "entries is not a list"
    return None


# ---------------------------------------------------------------------------
# XLA persistent compilation cache
# ---------------------------------------------------------------------------

_ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache: this file is <repo>/src/repro/core/warmstart.py
_CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no
    directory is set here.  Otherwise the cache goes to the fixed
    in-checkout path.  Thresholds drop to zero so even the engine's
    small resolver jits persist (the defaults skip sub-second compiles,
    which is exactly the population a simulator cold start is made of).
    """
    import jax

    path = os.environ.get(_ENV_COMPILE_CACHE, "").strip()
    if not path:
        path = _CHECKOUT_COMPILE_CACHE
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# One-call launcher wiring
# ---------------------------------------------------------------------------

def enable_warm_start(cache_dir: str | None = None) -> dict:
    """Load the lane snapshot under ``cache_dir`` (or env knob).

    Returns a small report ``{"cache_dir", "lanes"}``; with no directory
    configured it is a no-op reporting ``{"cache_dir": None, "lanes":
    0}`` so launchers can call it unconditionally.  The compile cache is
    not tied to ``cache_dir``: see :func:`enable_compilation_cache`.
    """
    cache_dir = cache_dir or cache_dir_from_env()
    if not cache_dir:
        return {"cache_dir": None, "lanes": 0}
    os.makedirs(cache_dir, exist_ok=True)
    return {"cache_dir": cache_dir, "lanes": load_lane_snapshot(cache_dir)}


def save_warm_start(cache_dir: str | None = None) -> int:
    """Persist the lane LRU under ``cache_dir`` (or env knob); returns
    entries written, or -1 when no directory is configured (no-op)."""
    cache_dir = cache_dir or cache_dir_from_env()
    if not cache_dir:
        return -1
    try:
        return save_lane_snapshot(cache_dir)
    except Exception as e:  # noqa: BLE001 - persistence is advisory
        # A failed save must never take the serve epilogue down with
        # it; the previous snapshot (if any) is still in place.
        faults.record_event("warmstart", "fault",
                            f"snapshot save failed: "
                            f"{type(e).__name__}: {e}")
        return -1
