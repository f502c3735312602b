"""Benchmark driver — one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows:
  fig4a/*      — GEMV speedup, no fence            (paper Fig. 4a)
  fig4b/*      — GEMV speedup, 150 ns fences       (paper Fig. 4b)
  reshape/*    — reshape-optimization gain          (paper §3.3)
  target/*     — deviation vs published 4096 numbers
  engine/*     — cycle-engine throughput (JAX vs oracle)
  fleet/*      — planning/resolution split, batched vs looped sweeps,
                 serve-replan lane-cache rows (fleet API)
  offload/*    — LLM decode offload case study (framework layer)
"""
from __future__ import annotations

# One XLA host device per core (up to 4), set before JAX initializes, so
# the fleet rows exercise the engine's multi-device lane sharding.
from ._xla_host_devices import force_host_devices

force_host_devices()


def main() -> None:
    from . import energy_fig, engine_speed, fleet_speed, paper_figs

    paper_figs.main()
    engine_speed.main()
    fleet_speed.main()
    energy_fig.main()

    # LLM decode offload case study (the paper's motivating workload)
    from repro.configs import ARCHS
    from repro.core.pimsim import PimSimulator
    from repro.serving.offload import OffloadPlanner
    sim = PimSimulator()
    for arch in ("granite-8b", "qwen2-72b", "granite-moe-3b-a800m",
                 "mamba2-130m"):
        tel = OffloadPlanner(ARCHS[arch], sim).decode_speedup(batch=1)
        print(f"offload/{arch}/b1,{tel['mixed_ns']/1e3:.1f},"
              f"{tel['speedup']:.3f}")


if __name__ == "__main__":
    main()
