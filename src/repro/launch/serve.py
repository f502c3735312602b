"""Serving launcher: continuous-batching decode + PIM offload telemetry.

The paper's kind is inference (LP5X-PIM accelerates decode GEMV), so this
is the primary end-to-end driver: it serves a model with batched
requests and reports, per decode step, what the LP5X-PIM offload would
deliver on the reference LPDDR5X-9600 x 4ch memory system.

By default it serves the reduced same-family model in float32
(``--smoke``); ``--no-smoke`` serves ``--arch`` at its published widths
with bfloat16 parameters — ``--arch gemma3-4b --no-smoke`` is the largest
token model of ``configs/`` that one 16 GB TPU v5e chip holds.

With ``--scenario`` the launcher becomes the closed-loop policy testbed:
a seeded workload (steady / bursty / diurnal / prefill-heavy /
drain-refill) drives the engine end to end under an adaptive offload
controller (``--policy per-step|hysteresis|sticky``) and the run reports
realized vs oracle speedup, decision switches and planner queries.

``--disagg`` serves through the disaggregated prefill/decode cell pair
(``serving/cells.py``) instead of the monolithic engine — optionally
bounded (``--prefill-budget`` / ``--handoff-bound`` /
``--admission-capacity``) and SLO-mixed (``--slo FRAC`` = latency-class
fraction, the rest throughput class with ``--starvation-age`` aging) —
and reports the handoff-queue and per-class telemetry on top of the
offload report.

``--daemon`` serves the scenario through the long-running
:class:`~repro.serving.daemon.ServeDaemon` (always the disaggregated
cell pair): asynchronous ingestion, drain/shutdown accounting, optional
SLO-driven decode autoscaling (``--autoscale``, bounded below by
``--min-slots``), an optional completion cap (``--max-requests``), and
streaming trace export (``--trace-out FILE`` writes tick-ordered JSONL
chunks in bounded memory; ``TraceWriter.load`` reassembles a trace
byte-identical to the in-memory path).

``--chaos`` runs the scenario under a seeded fault timeline
(``serving/chaos.py``, seed via ``--faults``): injected backend
failures, lane-cache poison/eviction storms, planner timeouts and
handoff pressure, absorbed by the degradation ladder.  The run must
complete with zero unhandled exceptions — results stay byte-exact by
the backend contract — and the report includes the incident record.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.core import engine as lane_engine
from repro.core import warmstart
from repro.core.pimsim import PimSimulator
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import OffloadPlanner
from repro.serving.policy import POLICIES, resolve_policy
from repro.serving.scenarios import (SCENARIOS, DisaggConfig,
                                     SpecDecodeConfig, assign_slo,
                                     make_scenario, resolve_scenario,
                                     run_scenario)


def _disagg_config(args) -> "DisaggConfig | bool":
    """The cell-pair config from the CLI knobs (False when not asked)."""
    if not args.disagg:
        if args.slo is not None:
            raise SystemExit("--slo requires --disagg (SLO classes are "
                             "a property of the cell pair's admission)")
        return False
    return DisaggConfig(prefill_budget=args.prefill_budget,
                        handoff_bound=args.handoff_bound,
                        starvation_age=args.starvation_age,
                        admission_capacity=args.admission_capacity)


def _print_disagg_report(rec: dict) -> None:
    hand = rec["handoff"]
    bound = hand["bound"] if hand["bound"] is not None else "unbounded"
    print(f"  KV handoff queue     : {hand['handoffs']} handoffs, peak "
          f"depth {hand['max_depth']} (bound {bound})")
    for cls, per in rec["per_class"].items():
        print(f"  SLO {cls:<11}      : {per['completed']}/"
              f"{per['submitted']} done, mean admit wait "
              f"{per['mean_admit_wait']:.2f} ticks, mean latency "
              f"{per['mean_completion_ticks']:.2f} ticks")


def run_scenario_mode(args, full_cfg, cfg, params, mesh=None,
                      t_start: float | None = None) -> None:
    planner = OffloadPlanner(full_cfg, PimSimulator())
    # Time-to-first-batch: main() entry through the first offload plan —
    # the window that contains every cold-start cost (XLA compiles, lane
    # resolves).  Parseable row; benchmarks/coldstart_smoke.py asserts a
    # warm process improves it.
    planner.plan(fence=args.fence)
    if t_start is not None:
        ttfb = time.perf_counter() - t_start
        print(f"serve/time_to_first_batch,{ttfb:.3f}", flush=True)
    spec = make_scenario(args.scenario, seed=args.seed, slots=args.slots,
                         quick=args.quick)
    disagg = _disagg_config(args)
    slo = (assign_slo(spec, frac_latency=args.slo)
           if args.slo is not None else None)
    spec_decode = (SpecDecodeConfig(draft_len=args.draft_len,
                                    acceptance=args.acceptance,
                                    seed=args.seed)
                   if args.scenario == "spec-decode" else None)
    t0 = time.perf_counter()
    if args.chaos:
        from repro.serving.chaos import run_chaos_scenario
        trace = run_chaos_scenario(cfg, params, planner, scenario=spec,
                                   seed=args.faults, policy=args.policy,
                                   fence=args.fence, mesh=mesh,
                                   disagg=disagg, slo=slo,
                                   spec_decode=spec_decode)
    else:
        trace = run_scenario(spec, cfg, params, planner,
                             policy=args.policy, fence=args.fence,
                             mesh=mesh, disagg=disagg, slo=slo,
                             spec_decode=spec_decode)
    dt = time.perf_counter() - t0
    rep = trace["controller"]
    mode = "disagg cells" if disagg else "monolithic engine"
    print(f"scenario {args.scenario} (seed={args.seed}, "
          f"{len(spec.arrivals)} requests, {args.slots} slots, {mode}) "
          f"under policy {args.policy}: {trace['tokens']} tokens in "
          f"{trace['steps']} steps ({dt:.2f}s host wall)")
    occ = ", ".join(f"{b}:{c}" for b, c in trace["occupancy"].items())
    print(f"  batch occupancy      : {occ}")
    print(f"  realized speedup     : {rep['realized_speedup']:.3f}x "
          f"(oracle {rep['oracle_speedup']:.3f}x, "
          f"efficiency {rep['efficiency']:.3f})")
    print(f"  decision switches    : {rep['switches']}; planner queries "
          f"{rep['planner_queries']}/{rep['steps']} steps; "
          f"replans {rep['replans']}")
    if disagg:
        _print_disagg_report(trace["disagg"])
    if "spec_decode" in trace:
        _print_spec_decode_report(trace["spec_decode"], planner, args)
    if args.chaos:
        _print_chaos_report(trace["chaos"])


def _print_spec_decode_report(rec: dict, planner, args) -> None:
    """Draft/verify accounting + a parseable ``serve/spec_decode`` row
    the CI job greps."""
    drafted = rec["drafted"]
    rate = rec["accepted"] / drafted if drafted else 0.0
    model = planner.spec_decode_speedup(draft_len=args.draft_len,
                                        acceptance=args.acceptance,
                                        fence=args.fence)
    print(f"  speculative decode   : {rec['rounds']} rounds, "
          f"{rec['accepted']}/{drafted} drafts accepted "
          f"({rate:.2f}), {rec['wasted']} wasted, "
          f"{rec['substeps']} verify sub-steps")
    print(f"  draft-lane model     : {model['speedup']:.3f}x per-token vs "
          f"vanilla decode ({model['tokens_per_round']:.2f} tok/round)")
    print(f"serve/spec_decode,rounds={rec['rounds']},"
          f"drafted={drafted},accepted={rec['accepted']},"
          f"wasted={rec['wasted']},substeps={rec['substeps']}", flush=True)


def _print_chaos_report(rec: dict) -> None:
    """Human summary + a parseable ``serve/chaos`` row the CI job greps
    (the run reaching this line at all means zero unhandled
    exceptions)."""
    by_kind: dict[str, int] = {}
    for ev in rec["events"]:
        by_kind[ev["kind"]] = by_kind.get(ev["kind"], 0) + 1
    kinds = ", ".join(f"{k}:{n}" for k, n in sorted(by_kind.items()))
    tripped = ",".join(rec["breaker"]["open"]) or "none"
    print(f"  chaos (faults seed {rec['seed']}): {rec['injected']} "
          f"injected over {len(rec['timeline'])} timeline actions")
    print(f"  incident events      : {kinds or 'none'}")
    print(f"  breaker              : threshold "
          f"{rec['breaker']['threshold']}, tripped {tripped}")
    print(f"serve/chaos,injected={rec['injected']},"
          f"events={len(rec['events'])},"
          f"degrades={by_kind.get('degrade', 0)},"
          f"trips={by_kind.get('trip', 0)},"
          f"sheds={by_kind.get('shed', 0)},unhandled=0", flush=True)


def run_daemon_mode(args, full_cfg, cfg, params, mesh=None) -> None:
    """Serve the scenario through :class:`ServeDaemon` and print the
    operational report (parseable ``serve/daemon`` row, ``unhandled=0``
    on a clean run — same convention as the chaos smoke)."""
    from repro.serving.daemon import ServeDaemon, TraceWriter
    from repro.serving.scenarios import AutoscaleConfig

    planner = OffloadPlanner(full_cfg, PimSimulator())
    planner.plan(fence=args.fence)
    spec = make_scenario(args.scenario, seed=args.seed, slots=args.slots,
                         quick=args.quick)
    dcfg = _disagg_config(args)
    slo = (assign_slo(spec, frac_latency=args.slo)
           if args.slo is not None else None)
    auto = (AutoscaleConfig(min_slots=args.min_slots)
            if args.autoscale else None)
    writer = (TraceWriter(args.trace_out)
              if args.trace_out is not None else None)
    t0 = time.perf_counter()
    with lane_engine.lane_mesh_scope(mesh):
        daemon = ServeDaemon(
            cfg, params, planner, scenario=spec, policy=args.policy,
            fence=args.fence,
            disagg=(dcfg if isinstance(dcfg, DisaggConfig) else None),
            slo=slo, autoscale=auto, max_requests=args.max_requests,
            writer=writer)
        rep = daemon.run()
    dt = time.perf_counter() - t0
    acct = rep["accounting"]
    print(f"daemon scenario {args.scenario} (seed={args.seed}, "
          f"{len(spec.arrivals)} requests, {args.slots} slots): "
          f"{acct['completed']} completed / {acct['shed']} shed / "
          f"{acct['dropped']} dropped in {rep['ticks']} ticks "
          f"({dt:.2f}s host wall)")
    if auto is not None:
        asr = rep["autoscale"]
        lims = asr["limits"] or [0]
        print(f"  autoscale            : limit {min(lims)}..{max(lims)} "
              f"over {len(lims)} ticks ({asr['grows']} grows, "
              f"{asr['shrinks']} shrinks, "
              f"{asr['slot_ticks']} slot-ticks provisioned)")
    if writer is not None:
        print(f"  streamed trace       : {writer.records} records in "
              f"{writer.flushes} chunks -> {args.trace_out}")
    print(f"serve/daemon,ingested={acct['ingested']},"
          f"completed={acct['completed']},shed={acct['shed']},"
          f"dropped={acct['dropped']},in_flight={acct['in_flight']},"
          f"ticks={rep['ticks']},unhandled=0", flush=True)


def main(argv: list[str] | None = None) -> dict | None:
    """Parse ``argv`` (default: the command line) and serve.

    The plain request path returns ``{"cfg", "params", "requests",
    "stats"}`` so an in-process caller can check what was served; the
    scenario and daemon paths return None.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=list(ARCHS))
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced same-family model in float32 "
                         "(default); --no-smoke serves --arch at its "
                         "published widths with bfloat16 parameters")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--fence", action="store_true", default=True)
    ap.add_argument("--scenario", default=None,
                    help="drive a seeded workload scenario end to end "
                         "under an adaptive offload controller "
                         f"(one of {sorted(SCENARIOS)}; underscores ok)")
    ap.add_argument("--policy", default="per-step",
                    help="offload control policy for --scenario runs "
                         f"(one of {sorted(POLICIES)}; underscores ok)")
    ap.add_argument("--draft-len", type=int, default=4, metavar="L",
                    help="with --scenario spec-decode: speculative draft "
                         "length per round")
    ap.add_argument("--acceptance", type=float, default=0.7, metavar="P",
                    help="with --scenario spec-decode: per-token draft "
                         "acceptance probability (seeded model)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="smaller scenario (CI smoke)")
    ap.add_argument("--disagg", action="store_true",
                    help="serve through the disaggregated prefill/decode "
                         "cell pair (serving/cells.py) instead of the "
                         "monolithic engine")
    ap.add_argument("--slo", type=float, default=None, metavar="FRAC",
                    help="with --disagg: fraction of requests in the "
                         "latency SLO class (rest are throughput class)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    metavar="N", help="with --disagg: max prefills per "
                    "tick (default unbounded)")
    ap.add_argument("--handoff-bound", type=int, default=None,
                    metavar="N", help="with --disagg: KV-handoff queue "
                    "bound (default unbounded)")
    ap.add_argument("--starvation-age", type=int, default=8, metavar="N",
                    help="with --disagg: ticks after which a waiting "
                    "throughput-class request outranks latency traffic")
    ap.add_argument("--admission-capacity", type=int, default=None,
                    metavar="N", help="with --disagg: admission-queue "
                    "capacity; arrivals over it shed the lowest SLO "
                    "class first (default unbounded, never sheds)")
    ap.add_argument("--daemon", action="store_true",
                    help="serve --scenario through the long-running "
                         "ServeDaemon (serving/daemon.py): async "
                         "ingestion, drain accounting, autoscaling and "
                         "streamed traces; implies --disagg")
    ap.add_argument("--max-requests", type=int, default=None, metavar="N",
                    help="with --daemon: auto-drain after N completed "
                         "requests (default: serve the whole scenario)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="with --daemon: stream the trace to FILE as "
                         "tick-ordered JSONL chunks (bounded memory) "
                         "instead of holding it in RAM")
    ap.add_argument("--autoscale", action="store_true",
                    help="with --daemon: grow/shrink the decode cell's "
                         "admission limit against per-class SLO wait "
                         "telemetry (AutoscaleConfig rule)")
    ap.add_argument("--min-slots", type=int, default=1, metavar="N",
                    help="with --autoscale: the admission-limit floor "
                         "(ceiling is the scenario's slot capacity)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the scenario under a seeded fault "
                         "timeline (serving/chaos.py); implies "
                         "--scenario chaos unless one is given")
    ap.add_argument("--faults", type=int, default=0, metavar="SEED",
                    help="with --chaos: fault-timeline seed (same seed, "
                         "same faults at the same ticks)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="run the PIM lane resolution as one shard_map "
                         "program over an N-device 'lanes' mesh (needs N "
                         "visible devices, e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N); "
                         "default: threaded multi-device dispatch")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="resolved-lane snapshot directory (warm "
                         "start); also via REPRO_CACHE_DIR.  The XLA "
                         "compile cache lives in JAX_COMPILATION_CACHE_DIR "
                         "or <repo>/.jax_cache")
    ap.add_argument("--lane-backend", default=None,
                    choices=["scan", "pallas", "auto"],
                    help="lane resolver backend (default: "
                         "REPRO_LANE_BACKEND env or scan); auto picks "
                         "from the platform, pallas fails with the "
                         "compiler's reason where the kernel cannot be "
                         "built")
    args = ap.parse_args(argv)
    if args.chaos and not args.scenario:
        args.scenario = "chaos"
    if args.daemon:
        if not args.scenario:
            ap.error("--daemon needs --scenario (the arrival process)")
        if args.chaos:
            ap.error("--daemon and --chaos are separate drivers; drive "
                     "chaos timelines through ServeDaemon's on_tick hook")
        args.disagg = True          # the daemon IS the cell pair
    for flag, name in ((args.max_requests, "--max-requests"),
                       (args.trace_out, "--trace-out")):
        if flag is not None and not args.daemon:
            ap.error(f"{name} requires --daemon")
    if args.autoscale and not args.daemon:
        ap.error("--autoscale requires --daemon")
    # Registry-backed validation instead of a frozen argparse ``choices``
    # list: underscore aliases resolve (``spec_decode`` works) and
    # unknown names fail with the full menu.
    try:
        if args.scenario:
            args.scenario = resolve_scenario(args.scenario)
        args.policy = resolve_policy(args.policy)
    except ValueError as e:
        ap.error(str(e))

    t_start = time.perf_counter()
    warmstart.enable_compilation_cache()
    lane_engine.configure_lane_backend(args.lane_backend)
    # Resolving here builds an explicitly requested pallas kernel, so a
    # platform where it cannot be built fails now, with the compiler's
    # reason, before any model work.
    print(f"lane backend: {lane_engine.resolved_lane_backend()} "
          f"(requested {lane_engine.lane_backend()})", flush=True)
    warm = warmstart.enable_warm_start(args.cache_dir)
    if warm["cache_dir"]:
        print(f"warm start: cache-dir {warm['cache_dir']} "
              f"({warm['lanes']} lanes loaded)", flush=True)

    full_cfg = ARCHS[args.arch]
    cfg = smoke_config(full_cfg) if args.smoke else full_cfg
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{cfg.name} serves stub embeddings; "
                         "see launch/dryrun.py for its decode cells")
    # The smoke model keeps float32 (every golden trace is pinned to
    # it); published widths are served in bfloat16, which is what lets
    # a 4B model fit one 16 GB chip.
    params = M.init_params(cfg, jax.random.PRNGKey(0),
                           dtype=jnp.float32 if args.smoke
                           else jnp.bfloat16)

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_lane_mesh
        mesh = make_lane_mesh(args.mesh)
        print(f"lane mesh: shard_map over {args.mesh} device(s)")

    if args.daemon:
        run_daemon_mode(args, full_cfg, cfg, params, mesh=mesh)
        _warm_epilogue(args)
        return None

    if args.scenario:
        run_scenario_mode(args, full_cfg, cfg, params, mesh=mesh,
                          t_start=t_start)
        _warm_epilogue(args)
        return None

    # Offload plan computed against the FULL architecture (the simulator
    # works on real matrix sizes regardless of the smoke model we run).
    lane_engine.configure_lane_mesh(mesh)
    planner = OffloadPlanner(full_cfg, PimSimulator())
    disagg = _disagg_config(args)
    if disagg:
        from repro.serving.cells import DisaggServingEngine
        from repro.serving.scenarios import SLO_LATENCY, SLO_THROUGHPUT
        eng = DisaggServingEngine(cfg, params, slots=args.slots,
                                  max_seq=128, disagg=disagg,
                                  planner=planner)
    else:
        eng = ServingEngine(cfg, params, slots=args.slots, max_seq=128,
                            planner=planner)
    rng = np.random.default_rng(0)
    frac = 1.0 if args.slo is None else args.slo
    reqs = []
    for i in range(args.requests):
        req = Request(rid=i,
                      prompt=rng.integers(0, cfg.vocab, size=4 + i % 8),
                      max_new=args.max_new)
        reqs.append(req)
        if disagg:
            eng.submit(req, slo=(SLO_LATENCY if rng.random() < frac
                                 else SLO_THROUGHPUT))
        else:
            eng.submit(req)
    t0 = time.perf_counter()
    stats = eng.run(max_steps=2000)
    dt = time.perf_counter() - t0
    mode = "disagg cells" if disagg else "monolithic engine"
    print(f"served {args.requests} requests ({mode}): {stats['tokens']} "
          f"tokens in {stats['steps']} steps ({dt:.2f}s host wall)")
    if disagg:
        _print_disagg_report(stats["disagg"])
    tel = stats["pim_telemetry"]
    print(f"PIM offload telemetry (arch={full_cfg.name}, "
          f"batch={tel['batch']}):")
    print(f"  decode GEMV time host-only : {tel['host_ns']/1e3:10.1f} us")
    print(f"  with LP5X-PIM offload      : {tel['mixed_ns']/1e3:10.1f} us")
    print(f"  speedup {tel['speedup']:.2f}x; offloaded "
          f"{len(tel['offloaded'])}/{tel['n_sites']} GEMV sites")
    _warm_epilogue(args)
    return dict(cfg=cfg, params=params, requests=reqs, stats=stats)


def _warm_epilogue(args) -> None:
    """Parseable lane-cache counters + snapshot save (no-op without a
    cache dir) — the cold-start smoke asserts against these rows."""
    info = lane_engine.lane_cache_info()
    print(f"serve/lane_cache,hits={info['hits']},misses={info['misses']},"
          f"size={info['size']}", flush=True)
    saved = warmstart.save_warm_start(args.cache_dir)
    if saved >= 0:
        print(f"warm start: saved {saved} lanes", flush=True)


if __name__ == "__main__":
    main()
