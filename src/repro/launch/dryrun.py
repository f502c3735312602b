import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import: jax locks the device
count at first initialization, and the production meshes need 512 host
placeholder devices (2 pods x 16 x 16).

Per cell this script:
  1. builds the step function (train_step / prefill / decode_step),
  2. lowers it under the production mesh with the sharding rules of
     `distribution.sharding` (ShapeDtypeStruct inputs — no allocation),
  3. compiles, records memory_analysis / cost_analysis / collective bytes,
  4. writes experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all [--mesh pod1|pod2|both]
"""
import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, shapes_for
from repro.distribution import sharding as SH
from repro.distribution.hlo_analysis import collective_bytes
from repro.distribution.roofline import model_flops
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.training.optimizer import AdamWState, adamw_init, adamw_update

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / \
    "dryrun"


def apply_variant(name: str):
    """§Perf hillclimb variants: flip one knob, re-lower, re-analyse."""
    from repro.distribution import roofline as RLmod
    from repro.distribution import sharding as SHmod
    from repro.models import layers as LAY
    from repro.models import moe as MOEmod
    from repro.launch import mesh as MESHmod
    SHmod.SERVE_TP_ONLY = False
    M.REMAT_POLICY = "full"
    M.CE_CHUNKS = 0
    M.QUANT_BITS = 0
    M.KV_QUANT = False
    MESHmod.MESH_OVERRIDE = None
    MOEmod.DISPATCH_SPEC = None
    LAY.FLASH_SKIP_BLOCKS = False
    RLmod.FLASH_SKIP_BLOCKS = False
    if name == "baseline":
        return
    if name == "serve-tp":
        SHmod.SERVE_TP_ONLY = True
    elif name == "serve-tp-w8":
        SHmod.SERVE_TP_ONLY = True
        M.QUANT_BITS = 8
    elif name == "serve-tp-w4":
        SHmod.SERVE_TP_ONLY = True
        M.QUANT_BITS = 4
    elif name == "serve-tp-w4-kv8":
        SHmod.SERVE_TP_ONLY = True
        M.QUANT_BITS = 4
        M.KV_QUANT = True
    elif name == "remat-dots":
        M.REMAT_POLICY = "dots"
    elif name == "remat-none":
        M.REMAT_POLICY = "none"
    elif name == "chunked-ce":
        M.CE_CHUNKS = 8
    elif name == "chunked-ce+dots":
        M.CE_CHUNKS = 8
        M.REMAT_POLICY = "dots"
    elif name == "moe-shard":
        from repro.models import moe as MOEmod
        MOEmod.DISPATCH_SPEC = ("data", None)
    elif name == "tp-save":
        M.REMAT_POLICY = "tp-save"
    elif name == "mesh-64x4":
        from repro.launch import mesh as MESHmod
        MESHmod.MESH_OVERRIDE = (64, 4)
    elif name == "moe-shard+save":
        from repro.models import moe as MOEmod
        MOEmod.DISPATCH_SPEC = ("data", None)
        M.REMAT_POLICY = "moe-save"
    elif name == "flash-skip":
        from repro.distribution import roofline as RLmod
        LAY.FLASH_SKIP_BLOCKS = True
        RLmod.FLASH_SKIP_BLOCKS = True
    elif name == "flash-skip+ce":
        from repro.distribution import roofline as RLmod
        LAY.FLASH_SKIP_BLOCKS = True
        RLmod.FLASH_SKIP_BLOCKS = True
        M.CE_CHUNKS = 8
    else:
        raise ValueError(f"unknown variant {name}")


def build_step(cfg, shape):
    """Returns (fn, arg_specs, arg_shardings) for the cell."""
    if shape.kind == "train":
        def train_step(params, opt, batch):
            def lf(p):
                return M.loss_fn(cfg, p, batch)[0]
            loss, grads = jax.value_and_grad(lf)(params)
            params, opt = adamw_update(params, grads, opt, lr=3e-4)
            return params, opt, loss
        return train_step, "train"
    if shape.kind == "prefill":
        def prefill_step(params, batch, cache):
            return M.prefill(cfg, params, batch, cache)
        return prefill_step, "prefill"

    def decode(params, cache, token, pos):
        return M.decode_step(cfg, params, cache, token, pos)
    return decode, "decode"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg_override=None):
    cfg = cfg_override or ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    pdtype = jnp.bfloat16
    pspecs = M.param_specs(cfg, pdtype)
    pshard = SH.param_shardings(cfg, mesh, kind=shape.kind)
    ispecs = M.input_specs(cfg, shape, pdtype)
    ishard = SH.input_shardings(cfg, mesh, shape)
    fn, kind = build_step(cfg, shape)

    with mesh:
        if kind == "train":
            opt_specs = jax.eval_shape(adamw_init, pspecs)
            opt_shard = AdamWState(
                step=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                m=pshard, v=pshard)
            jfn = jax.jit(fn, in_shardings=(pshard, opt_shard,
                                            ishard["batch"]),
                          donate_argnums=(0, 1))
            lowered = jfn.lower(pspecs, opt_specs, ispecs["batch"])
        elif kind == "prefill":
            jfn = jax.jit(fn, in_shardings=(pshard, ishard["batch"],
                                            ishard["cache"]),
                          donate_argnums=(2,))
            lowered = jfn.lower(pspecs, ispecs["batch"], ispecs["cache"])
        else:
            jfn = jax.jit(fn, in_shardings=(pshard, ishard["cache"],
                                            ishard["token"], ishard["pos"]),
                          donate_argnums=(1,))
            lowered = jfn.lower(pspecs, ispecs["cache"], ispecs["token"],
                                ispecs["pos"])
    return lowered, mesh, cfg, shape


def cost_extrapolate(arch: str, shape_name: str, multi_pod: bool) -> dict:
    """Exact per-device FLOPs/bytes from compiled *unrolled* small-L
    variants: total(L) = base + L x per_layer.

    XLA's cost_analysis counts a while body once regardless of trip
    count, so the full-L scanned compile cannot report total cost; two
    fully-unrolled variants (nl, 2nl layers; nl a multiple of the
    local:global period) recover base and per-layer exactly.
    """
    cfg = ARCHS[arch]
    # Layer kinds (local/global) only affect attention, which is added
    # analytically — 2/4-layer probes capture the matmul terms exactly.
    nl_a = 2
    nl_b = 4
    vals = {}
    M.UNROLL_SCAN = True
    try:
        for nl in (nl_a, nl_b):
            cfg2 = dataclasses.replace(cfg, n_layers=nl)
            lowered, mesh, _, _ = lower_cell(arch, shape_name, multi_pod,
                                             cfg_override=cfg2)
            cost = lowered.compile().cost_analysis()
            vals[nl] = (float(cost.get("flops", 0.0)),
                        float(cost.get("bytes accessed", 0.0)))
    finally:
        M.UNROLL_SCAN = False
    fa, ba = vals[nl_a]
    fb, bb = vals[nl_b]
    per_layer_f = (fb - fa) / (nl_b - nl_a)
    per_layer_b = (bb - ba) / (nl_b - nl_a)
    flops_dev = fa - nl_a * per_layer_f + cfg.n_layers * per_layer_f
    bytes_dev = ba - nl_a * per_layer_b + cfg.n_layers * per_layer_b
    # Blockwise-attention inner scans are counted once by cost_analysis;
    # add the white-box executed-block account (see roofline module —
    # this also makes block-skipping optimizations measurable).
    from repro.distribution.roofline import attention_hlo_flops
    shape = SHAPES[shape_name]
    mesh_chips = 512 if multi_pod else 256
    attn = attention_hlo_flops(cfg, shape)
    return dict(
        flops_dev=flops_dev + attn["added_global"] / mesh_chips,
        bytes_dev=bytes_dev,
        matmul_flops_dev=flops_dev,
        attn_flops_global=attn["total_global"],
        attn_counted_once_global=attn["counted_once_global"],
        probe_layers=[nl_a, nl_b],
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, extrapolate: bool = True,
             variant: str = "baseline") -> dict:
    mesh_name = "pod2" if multi_pod else "pod1"
    apply_variant(variant)
    t0 = time.perf_counter()
    rec = dict(arch=arch, shape=shape_name, mesh=mesh_name,
               variant=variant, status="ok")
    try:
        lowered, mesh, cfg, shape = lower_cell(arch, shape_name, multi_pod)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo, scan_trip_count=cfg.n_layers)
        chips = mesh.size
        rec.update(
            chips=chips,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            flops=float(cost.get("flops", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", 0.0)),
            collective=coll,
            model_flops=model_flops(cfg, shape),
            n_layers=cfg.n_layers,
            memory=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
                output_bytes=getattr(mem, "output_size_in_bytes", 0),
                temp_bytes=getattr(mem, "temp_size_in_bytes", 0),
                peak_bytes=getattr(mem, "peak_memory_in_bytes", 0),
            ),
            hlo_collective_ops={
                k: v for k, v in coll.items() if k != "total"},
        )
        # per-device view (the dry-run proves it fits)
        rec["per_device_arg_gib"] = rec["memory"]["argument_bytes"] / \
            chips / 2**30
        if extrapolate:
            rec["extrap"] = cost_extrapolate(arch, shape_name, multi_pod)
            rec["extrap"]["variant"] = variant
    except Exception as e:  # noqa: BLE001 - record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.perf_counter() - t0, 1)
    apply_variant("baseline")
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        path = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
        path.write_text(json.dumps(rec, indent=1))
    return rec


def pim_offload_report(arch: str, batches=(1, 2, 4, 8, 16),
                       scenario: str | None = None,
                       policy: str = "per-step",
                       disagg: bool = False) -> dict:
    """Decode-phase PIM offload telemetry across a hardware-variant grid.

    One ``OffloadPlanner.plan_grid`` call — i.e. a single batched engine
    dispatch — covers every (spec variant x GEMV site) point of this
    model; per variant we record the plan and the end-to-end decode-step
    speedup curve over batch sizes.  With ``scenario`` the report also
    runs the adaptive offload controller closed-loop over that
    scenario's simulated occupancy trace (no model involved) and records
    realized-vs-oracle policy telemetry; the ``spec-decode`` scenario
    instead drives the loop from ``simulate_spec_decode``'s occupancy
    (acceptance-dependent slot dynamics) and records the draft/verify
    accounting.  With ``disagg`` the closed
    loop instead runs over the disaggregated cell pair's decode
    occupancy (``simulate_disagg`` — bounded prefill/handoff, SLO-mixed
    admission, still model-free) and the record gains the handoff/SLO
    scheduling telemetry.  The report always closes with the
    heterogeneous spec-family sweep (``configs/specfam.py``): one
    ``plan_grid`` dispatch over the whole population, then each
    family's offload frontier and speculative-decode economics.
    Writes experiments/dryrun/pim/<arch>.json.
    """
    import dataclasses as _dc

    from repro.configs.specfam import SPEC_FAMILIES
    from repro.core.timing import DEFAULT_SYSTEM, LpddrTimings, PimSpec, \
        SystemSpec
    from repro.serving.offload import OffloadPlanner
    from repro.serving.scenarios import DisaggConfig, SpecDecodeConfig, \
        assign_slo, make_scenario, occupancy_trace, resolve_scenario, \
        run_policy_over_trace, simulate_disagg, simulate_spec_decode

    variants = {
        "lp5x-9600": DEFAULT_SYSTEM,
        "fast-core": SystemSpec(timings=LpddrTimings(tRCD=15.0, tRP=15.0)),
        "mac2": SystemSpec(pim=PimSpec(mac_interval_ck=2)),
        "srf1k": SystemSpec(pim=PimSpec(srf_bytes=1024)),
    }
    planner = OffloadPlanner(ARCHS[arch])
    grid = planner.plan_grid(list(variants.values()))
    rec: dict = dict(arch=arch, variants={})
    for (name, spec), decisions in zip(variants.items(), grid):
        rec["variants"][name] = dict(
            sites=[{**_dc.asdict(d.site), "pim_ns": d.pim_ns,
                    "host_ns": d.host_ns, "reshape": d.reshape,
                    "offload_below_batch": d.offload_below_batch}
                   for d in decisions],
            # str keys: the in-memory record matches its JSON round-trip
            decode_speedup={str(b): planner.decode_speedup(batch=b,
                                                           spec=spec)
                            for b in batches},
        )
    if scenario:
        scenario = resolve_scenario(scenario)
        sc = make_scenario(scenario, seed=0, quick=True)
        if scenario == "spec-decode":
            sd = SpecDecodeConfig()
            sim = simulate_spec_decode(sc, sd)
            occ = [b for b in sim["per_tick_batch"] if b > 0]
            drafted = sum(sim["drafted"].values())
            accepted = sum(sim["accepted"].values())
            rec["spec_decode"] = dict(
                config=sd.to_record(), drafted=drafted, accepted=accepted,
                wasted=drafted - accepted,
                rounds=sum(sim["rounds"].values()),
                model=planner.spec_decode_speedup(
                    draft_len=sd.draft_len, acceptance=sd.acceptance))
        else:
            occ = occupancy_trace(sc)
        controller = run_policy_over_trace(planner, policy, occ)
        rec["serving_policy"] = dict(scenario=scenario, policy=policy,
                                     report=controller.report())
        if disagg:
            # The cell pair's decode occupancy under bounded prefill,
            # a bounded KV-handoff queue and a mixed SLO population —
            # the policy sees what the disagg decode cell would show it.
            dcfg = DisaggConfig(prefill_budget=2, handoff_bound=3,
                                starvation_age=4)
            slo = assign_slo(sc, frac_latency=0.5)
            sim = simulate_disagg(sc, dcfg, slo)
            dec = [b for b in sim["per_tick_batch"] if b > 0]
            dctl = run_policy_over_trace(planner, policy, dec)
            rec["disagg"] = dict(
                scenario=scenario, policy=policy,
                config=dcfg.to_record(),
                slo={str(r): s for r, s in sorted(slo.items())},
                max_handoff_depth=sim["max_handoff_depth"],
                decode_steps=len(dec),
                report=dctl.report())
    # Heterogeneous spec-family sweep: the whole population's decisions
    # come from ONE batched grid dispatch; frontiers and spec-decode
    # economics per family are then cache lookups + arithmetic.
    planner.plan_grid(list(SPEC_FAMILIES.values()))
    rec["spec_families"] = {
        name: dict(frontier=planner.frontier(spec=s),
                   spec_decode=planner.spec_decode_speedup(spec=s))
        for name, s in SPEC_FAMILIES.items()}
    out_dir = OUT_DIR / "pim"
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{arch}.json").write_text(json.dumps(rec, indent=1))
    return rec


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch, cfg in ARCHS.items():
        for shape_name in shapes_for(cfg):
            cells.append((arch, shape_name))
    return cells


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod1",
                    help="pod1|pod2|both selects the production mesh for "
                         "cell lowering; with --pim an integer N instead "
                         "runs the offload grid's lane resolution as one "
                         "shard_map program over an N-device 'lanes' mesh")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--pim", action="store_true",
                    help="emit decode-phase PIM offload telemetry per arch "
                         "(multi-spec grid, one batched engine query) "
                         "instead of lowering/compiling cells")
    from repro.serving.policy import POLICIES
    from repro.serving.scenarios import SCENARIOS
    ap.add_argument("--scenario", default=None,
                    help="with --pim: also run the adaptive offload "
                         "controller closed-loop over this scenario's "
                         "simulated occupancy trace "
                         f"(one of {sorted(SCENARIOS)}; underscores ok)")
    ap.add_argument("--policy", default="per-step",
                    help="with --pim --scenario: offload control policy "
                         f"(one of {sorted(POLICIES)}; underscores ok)")
    ap.add_argument("--disagg", action="store_true",
                    help="with --pim: run the closed loop over the "
                         "disaggregated cell pair's decode occupancy "
                         "(bounded prefill/handoff, SLO-mixed admission; "
                         "defaults --scenario to bursty)")
    ap.add_argument("--extrap-only", action="store_true",
                    help="recompute the probe extrapolation of existing "
                         "cells (methodology changes) without the full "
                         "compile")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="resolved-lane snapshot directory (warm "
                         "start); also via REPRO_CACHE_DIR")
    args = ap.parse_args()
    # Registry-backed validation (underscore aliases resolve; unknown
    # names fail with the full menu) instead of frozen argparse choices.
    from repro.serving.policy import resolve_policy
    from repro.serving.scenarios import resolve_scenario
    try:
        if args.scenario:
            args.scenario = resolve_scenario(args.scenario)
        args.policy = resolve_policy(args.policy)
    except ValueError as e:
        ap.error(str(e))

    from repro.core import warmstart
    warmstart.enable_compilation_cache()
    warm = warmstart.enable_warm_start(args.cache_dir)
    if warm["cache_dir"]:
        print(f"warm start: cache-dir {warm['cache_dir']} "
              f"({warm['lanes']} lanes loaded)", flush=True)

    if args.pim:
        if not args.all and args.arch not in ARCHS:
            ap.error(f"--pim needs --all or --arch from {list(ARCHS)}")
        if args.disagg and args.scenario is None:
            args.scenario = "bursty"
        if args.mesh.isdigit():
            from repro.core import engine as lane_engine
            from repro.launch.mesh import make_lane_mesh
            lane_engine.configure_lane_mesh(make_lane_mesh(int(args.mesh)))
            print(f"[pim] lane mesh: shard_map over {args.mesh} device(s)",
                  flush=True)
        elif args.mesh != "pod1":
            ap.error("--pim takes an integer --mesh N (shard_map lane "
                     "mesh); pod meshes apply to cell lowering only")
        archs = list(ARCHS) if args.all else [args.arch]
        for arch in archs:
            rec = pim_offload_report(arch, scenario=args.scenario,
                                     policy=args.policy,
                                     disagg=args.disagg)
            base = rec["variants"]["lp5x-9600"]["decode_speedup"]["1"]
            print(f"[pim] {arch}: decode b=1 speedup "
                  f"{base['speedup']:.2f}x, "
                  f"{len(base['offloaded'])}/{base['n_sites']} sites",
                  flush=True)
            if "serving_policy" in rec:
                rep = rec["serving_policy"]["report"]
                print(f"[pim] {arch}: {args.scenario} x {args.policy}: "
                      f"realized {rep['realized_speedup']:.2f}x / oracle "
                      f"{rep['oracle_speedup']:.2f}x (eff "
                      f"{rep['efficiency']:.3f}), "
                      f"{rep['planner_queries']} queries over "
                      f"{rep['steps']} steps", flush=True)
            if "spec_decode" in rec:
                sdr = rec["spec_decode"]
                print(f"[pim] {arch}: spec-decode "
                      f"{sdr['accepted']}/{sdr['drafted']} drafts "
                      f"accepted, model "
                      f"{sdr['model']['speedup']:.2f}x/token", flush=True)
            for fam, frec in rec["spec_families"].items():
                n_pim = sum(1 for b in frec["frontier"].values() if b > 1)
                print(f"[pim] {arch}: family {fam}: {n_pim}/"
                      f"{len(frec['frontier'])} sites PIM-favored, "
                      f"spec-decode "
                      f"{frec['spec_decode']['speedup']:.2f}x/token",
                      flush=True)
            if "disagg" in rec:
                drep = rec["disagg"]["report"]
                print(f"[pim] {arch}: disagg cells x {args.policy}: eff "
                      f"{drep['efficiency']:.3f} over "
                      f"{drep['steps']} decode steps, peak handoff "
                      f"depth {rec['disagg']['max_handoff_depth']}",
                      flush=True)
        warmstart.save_warm_start(args.cache_dir)
        sys.exit(0)

    if args.disagg:
        ap.error("--disagg applies to --pim runs only")
    if args.mesh not in ("pod1", "pod2", "both"):
        ap.error("--mesh must be pod1|pod2|both for cell lowering "
                 "(integer lane-mesh sizes apply to --pim only)")
    meshes = {"pod1": [False], "pod2": [True],
              "both": [False, True]}[args.mesh]
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            mesh_name = "pod2" if mp else "pod1"
            out = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
            if args.skip_existing and out.exists():
                prev = json.loads(out.read_text())
                if prev.get("status") == "ok":
                    print(f"[skip] {arch} {shape_name} {mesh_name}")
                    continue
            if args.extrap_only:
                if not out.exists():
                    continue
                rec = json.loads(out.read_text())
                if rec.get("status") != "ok":
                    continue
                apply_variant(args.variant)
                try:
                    rec["extrap"] = cost_extrapolate(arch, shape_name, mp)
                    rec["model_flops"] = model_flops(
                        ARCHS[arch], SHAPES[shape_name])
                    out.write_text(json.dumps(rec, indent=1))
                    print(f"[extrap] {arch} {shape_name} {mesh_name}: "
                          f"{rec['extrap']['flops_dev']:.3e} flops/dev",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    print(f"[error] extrap {arch} {shape_name} "
                          f"{mesh_name}: {e}", flush=True)
                apply_variant("baseline")
                continue
            rec = run_cell(arch, shape_name, mp, variant=args.variant)
            ok = rec["status"] == "ok"
            failures += (not ok)
            msg = (f"{rec['flops']:.3e} flops, "
                   f"coll {rec['collective']['total']:.3e} B, "
                   f"compile {rec['compile_s']}s" if ok
                   else rec.get("error", "?"))
            print(f"[{rec['status']}] {arch} {shape_name} {mesh_name}: "
                  f"{msg}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
