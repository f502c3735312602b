"""Bring-up smoke test on one TPU chip: the simulator and a full-width server.

Runs the repository's two JAX programs through their normal entry points,
in this one process (a chip belongs to one process at a time):

1. Device check: print platform, device kind and count; refuse anything
   that is not a TPU.
2. Simulator: the golden (spec x shape) grid and the paper's Fig. 4 W8A8
   grid through ``PimExecutor.run_many`` with a cold lane LRU.  Golden
   records must equal ``tests/golden/fleet_parity.json`` exactly, Fig. 4
   cycles must equal the pure-Python ``RefEngine`` on the smallest
   points, and no resolve may step down the degradation ladder.
3. Serve: ``repro.launch.serve --arch gemma3-4b --no-smoke`` (34 layers,
   d_model 2560, vocab 262144, bfloat16 parameters) answers 8 requests on
   4 slots; then prefill followed by cached decode must match a full
   ``forward`` over the same tokens within ``BF16_LOGIT_BOUND``, and the
   offload planner for the same architecture must resolve without a
   ladder step-down.

``--four-chips`` instead runs only the multi-device lane path: both
simulator grids threaded over four devices and under a 4-device lane
mesh, compared with single-device totals and the golden file, with the
lane rows each device resolved printed.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips of one host

The last line printed is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GOLDEN = os.path.join(ROOT, "tests", "golden", "fleet_parity.json")

# Prefill-then-decode vs full forward, bfloat16 parameters: the largest
# logit difference over the largest reference logit.  bfloat16 keeps 8
# significant bits, so one rounding step near the largest logit is 2^-7
# of it; the two paths reduce in different orders through 34 layers.
# 2^-4 allows eight such steps — a wrong cache position or mask moves
# logits by the order of the logits themselves.
BF16_LOGIT_BOUND = 2.0 ** -4

SERVE_ARGV = ["--arch", "gemma3-4b", "--no-smoke", "--requests", "8",
              "--slots", "4", "--max-new", "16"]

# Fig. 4 points small enough for the pure-Python oracle.
REF_DIM = 512


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def device_check(want_count: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        _fail(f"no TPU: JAX found platform {dev['platform']!r}")
    if dev["count"] < want_count:
        _fail(f"need {want_count} devices, found {dev['count']}")
    return dev


def _no_degrade(phase: str) -> None:
    from repro.core import faults

    bad = [e for e in faults.events() if e["kind"] == "degrade"]
    if bad:
        _fail(f"{phase}: lane resolution stepped down the ladder: {bad}")


def _timed_run_many(reqs) -> tuple[list, float, float]:
    """Resolve ``reqs`` twice from a cold lane LRU: the first call
    includes compilation, the second is the compiled steady state.
    ``run_many`` returns host integers, so each clock stops only after
    the device results are ready (the same wait as block_until_ready)."""
    from repro.core import engine
    from repro.pimkernel.executor import PimExecutor

    engine.lane_cache_clear()
    t0 = time.perf_counter()
    PimExecutor().run_many(reqs)
    first_s = time.perf_counter() - t0
    engine.lane_cache_clear()
    t0 = time.perf_counter()
    res = PimExecutor().run_many(reqs)
    steady_s = time.perf_counter() - t0
    return res, first_s, steady_s


def _golden_records(results) -> dict:
    from repro.pimkernel.grids import golden_record, golden_requests

    labels = [label for label, _ in golden_requests()]
    # JSON round trip normalizes float repr on both sides of the diff.
    return json.loads(json.dumps({label: golden_record(r)
                                  for label, r in zip(labels, results)}))


def _check_golden(results, phase: str) -> None:
    with open(GOLDEN, encoding="utf-8") as f:
        fixture = json.load(f)
    got = _golden_records(results)
    if got != fixture:
        drift = sorted(k for k in fixture if got.get(k) != fixture[k])
        _fail(f"{phase}: golden drift at {drift}")


def _ref_cycles(reqs) -> list[int]:
    """Cycles of ``reqs`` from ``RefEngine`` (max over channel totals)."""
    from repro.core.engine_ref import RefEngine
    from repro.pimkernel.executor import PimExecutor

    out, memo = [], {}
    for p in PimExecutor().plan_many(reqs):
        totals = []
        for s in p.streams:
            key = (p.ctx.cyc, s.tobytes())
            if key not in memo:
                memo[key] = RefEngine(p.ctx.cyc, validate=False).run(s)[1]
            totals.append(memo[key])
        out.append(max(totals))
    return out


def simulator_phase() -> None:
    from repro.core import engine
    from repro.pimkernel.grids import fig4_grid, golden_requests
    from repro.pimkernel.tileconfig import PimDType

    print(f"lane backend: {engine.resolved_lane_backend()} (requested "
          f"{engine.lane_backend()}), ladder {engine.ladder_rungs()}",
          flush=True)
    golden = [r for _, r in golden_requests()]
    res, first_s, steady_s = _timed_run_many(golden)
    _check_golden(res, "golden grid")
    print(f"simulator/golden: {len(golden)} points byte-equal to "
          f"{os.path.relpath(GOLDEN, ROOT)}; first call (compile + run) "
          f"{first_s:.3f} s, steady {steady_s:.3f} s", flush=True)

    fig4 = fig4_grid(dtypes=[PimDType.W8A8])
    compiles = engine.compile_cache_size()
    res, first_s, steady_s = _timed_run_many(fig4)
    print(f"simulator/fig4_w8a8: {len(fig4)} points, "
          f"{engine.compile_cache_size() - compiles} new executables; "
          f"first call (compile + run) {first_s:.3f} s, steady "
          f"{steady_s:.3f} s", flush=True)
    idx = [i for i, r in enumerate(fig4) if REF_DIM in (r.H, r.W)]
    ref = _ref_cycles([fig4[i] for i in idx])
    got = [res[i].cycles for i in idx]
    if got != ref:
        _fail(f"fig4 cycles differ from RefEngine: {got} vs {ref}")
    print(f"simulator/fig4_w8a8: {len(idx)} points (dim {REF_DIM}) "
          f"equal RefEngine", flush=True)
    _no_degrade("simulator")


def serve_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ARCHS
    from repro.core.pimsim import PimSimulator
    from repro.launch import serve
    from repro.models import model as M
    from repro.serving.offload import OffloadPlanner

    print(f"serve: python -m repro.launch.serve {' '.join(SERVE_ARGV)}",
          flush=True)
    t0 = time.perf_counter()
    out = serve.main(SERVE_ARGV)
    serve_s = time.perf_counter() - t0
    cfg, params, reqs = out["cfg"], out["params"], out["requests"]
    dtypes = {str(a.dtype) for a in jax.tree.leaves(params)}
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"serve: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
          f"vocab {cfg.vocab}, {n_params / 1e9:.3f} B params {dtypes}; "
          f"main() {serve_s:.3f} s (compiles included)", flush=True)
    if dtypes != {"bfloat16"}:
        _fail(f"full-width params are {dtypes}, want bfloat16")
    max_new = int(SERVE_ARGV[SERVE_ARGV.index("--max-new") + 1])
    for r in reqs:
        if not r.done or len(r.out) != max_new:
            _fail(f"request {r.rid}: done={r.done}, {len(r.out)} of "
                  f"{max_new} tokens")
        if not all(0 <= t < cfg.vocab_padded for t in r.out):
            _fail(f"request {r.rid}: token out of range: {r.out}")
    print(f"serve: {len(reqs)} requests complete, {max_new} tokens each",
          flush=True)

    # Prefill then cached decode vs one full forward over the same
    # tokens, with the server's parameters and cache dtype.
    rng = np.random.default_rng(0)
    b, s = 2, 24
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, size=(b, s)), jnp.int32)
    full, _ = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t},
                                             remat=False))(params, tokens)
    cache = M.init_cache(cfg, b, s + 8, jnp.float32)
    logits_p, cache = jax.jit(lambda p, t, c: M.prefill(
        cfg, p, {"tokens": t}, c))(params, tokens[:, :-1], cache)
    logits_d, _ = jax.jit(lambda p, c, t, q: M.decode_step(
        cfg, p, c, t, q))(params, cache, tokens[:, -1:],
                          jnp.asarray(s - 1, jnp.int32))
    full = np.asarray(jax.block_until_ready(full), np.float32)

    def rel_diff(got, want) -> float:
        return float(np.abs(got - want).max() / np.abs(want).max())

    for name, got, want in (("prefill", logits_p, full[:, -2]),
                            ("decode", logits_d, full[:, -1])):
        got = np.asarray(jax.block_until_ready(got), np.float32)
        if not (np.isfinite(got).all() and np.isfinite(want).all()):
            _fail(f"{name}: non-finite logits")
        rel = rel_diff(got, want)
        print(f"serve/{name}_vs_forward: max |diff| / max |logit| = "
              f"{rel:.6f} (bound {BF16_LOGIT_BOUND})", flush=True)
        if not rel <= BF16_LOGIT_BOUND:
            _fail(f"{name} logits differ from forward by {rel:.6f}")
    # Negative control: the decode logits against the previous position
    # must fail the bound, or the comparison could not catch an
    # off-by-one cache position.
    control = rel_diff(np.asarray(logits_d, np.float32), full[:, -2])
    print(f"serve/control_decode_vs_previous_position: {control:.6f}",
          flush=True)
    if not control > BF16_LOGIT_BOUND:
        _fail(f"the bound cannot tell adjacent positions apart: {control}")

    t0 = time.perf_counter()
    planner = OffloadPlanner(ARCHS["gemma3-4b"], PimSimulator())
    plan = planner.plan()
    tel = planner.decode_speedup(batch=4)
    print(f"serve/offload_plan: {len(plan)} GEMV sites planned in "
          f"{time.perf_counter() - t0:.3f} s, batch-4 speedup "
          f"{tel['speedup']:.4f}x", flush=True)
    _no_degrade("serve")


def four_chip_phase() -> None:
    from repro.core import engine
    from repro.pimkernel.grids import fig4_grid, golden_requests
    from repro.pimkernel.executor import PimExecutor
    from repro.pimkernel.tileconfig import PimDType

    golden = [r for _, r in golden_requests()]
    fig4 = fig4_grid(dtypes=[PimDType.W8A8])
    grids = {"golden": golden, "fig4_w8a8": fig4}

    def totals(label: str) -> dict:
        out = {}
        for name, reqs in grids.items():
            before = engine.slab_device_rows()
            engine.lane_cache_clear()
            t0 = time.perf_counter()
            res = PimExecutor().run_many(reqs)
            dt = time.perf_counter() - t0
            after = engine.slab_device_rows()
            rows = {d: n - before.get(d, 0) for d, n in after.items()
                    if n != before.get(d, 0)}
            print(f"four_chips/{label}/{name}: {len(reqs)} points in "
                  f"{dt:.3f} s (compiles included); lane rows per device "
                  f"{rows}", flush=True)
            out[name] = res
        return out

    engine.configure_lane_devices(1)
    single = totals("single_device")
    engine.configure_lane_devices(None)
    print(f"lane devices: {[str(d) for d in engine.lane_devices()]}, "
          f"ladder {engine.ladder_rungs()}", flush=True)
    threaded = totals("threaded")
    engine.configure_lane_mesh(4)
    print(f"lane mesh: {engine.lane_mesh()}, ladder "
          f"{engine.ladder_rungs()}", flush=True)
    meshed = totals("mesh")
    engine.configure_lane_mesh(None)
    for label, run in (("single_device", single), ("threaded", threaded),
                       ("mesh", meshed)):
        _check_golden(run["golden"], f"{label} golden grid")
        for name in grids:
            want = [r.cycles for r in single[name]]
            if [r.cycles for r in run[name]] != want:
                _fail(f"{label}/{name} cycles differ from single device")
            if [r.energy for r in run[name]] != \
                    [r.energy for r in single[name]]:
                _fail(f"{label}/{name} energy differs from single device")
    print("four_chips: threaded and mesh totals equal single-device "
          "totals and the golden file", flush=True)
    _no_degrade("four_chips")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-device lane path on 4 chips")
    args = ap.parse_args()
    dev = device_check(4 if args.four_chips else 1)

    from repro.core import warmstart

    print(f"compile cache: {warmstart.enable_compilation_cache()}",
          flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        simulator_phase()
        serve_phase()
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
