"""Cold-start smoke: serve twice, assert warm start actually warms.

Runs ``repro.launch.serve --scenario bursty --quick`` in two fresh
subprocesses sharing one lane-snapshot ``--cache-dir`` (and the launcher's
XLA compile cache, in ``JAX_COMPILATION_CACHE_DIR`` or the checkout's
``.jax_cache``).  The first process resolves every lane cold, then
snapshots; the parent never imports JAX, so each child may hold the
device.  The second must

* report a strictly better ``serve/time_to_first_batch`` (compile cache
  + lane snapshot replace the dominant cold costs), and
* do ZERO lane re-resolves for cached keys — ``serve/lane_cache``
  misses == 0, every telemetry lane replayed from the snapshot.

Exit status is the assertion: CI runs this as the cold-start gate.
Usage: ``python benchmarks/coldstart_smoke.py [--scenario bursty]``.
"""
from __future__ import annotations

import argparse
import re
import subprocess
import sys
import tempfile


def _parse(out: str) -> dict:
    m_ttfb = re.search(r"^serve/time_to_first_batch,([\d.]+)$", out, re.M)
    m_cache = re.search(
        r"^serve/lane_cache,hits=(\d+),misses=(\d+),size=(\d+)$", out, re.M)
    if not (m_ttfb and m_cache):
        raise SystemExit(f"serve output missing parseable rows:\n{out}")
    return dict(ttfb=float(m_ttfb.group(1)),
                hits=int(m_cache.group(1)),
                misses=int(m_cache.group(2)),
                size=int(m_cache.group(3)))


def run_once(cache_dir: str, scenario: str) -> dict:
    cmd = [sys.executable, "-m", "repro.launch.serve",
           "--scenario", scenario, "--quick", "--cache-dir", cache_dir]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(f"serve failed:\n{proc.stdout}\n{proc.stderr}")
    return _parse(proc.stdout)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="bursty")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory(prefix="repro-coldstart-") as d:
        cold = run_once(d, args.scenario)
        warm = run_once(d, args.scenario)

    print(f"coldstart/ttfb_cold,{cold['ttfb']:.3f},misses={cold['misses']}")
    print(f"coldstart/ttfb_warm,{warm['ttfb']:.3f},misses={warm['misses']}")
    print(f"coldstart/ttfb_speedup,{warm['ttfb']:.3f},"
          f"{cold['ttfb'] / warm['ttfb']:.2f}")

    assert warm["misses"] == 0, \
        (f"warm serve re-resolved {warm['misses']} lanes that the "
         f"snapshot should have replayed (cold run had "
         f"{cold['misses']} misses)")
    assert warm["ttfb"] < cold["ttfb"], \
        (f"warm time-to-first-batch {warm['ttfb']:.3f}s did not improve "
         f"on cold {cold['ttfb']:.3f}s")
    print("coldstart smoke OK")


if __name__ == "__main__":
    main()
