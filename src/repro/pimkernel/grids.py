"""Request grids shared by the tests, the benchmarks and ``chip_smoke.py``.

* The golden parity grid: 2 ``SystemSpec`` variants x 4 shapes whose
  cycle/energy records are pinned byte-for-byte in
  ``tests/golden/fleet_parity.json``.
* The paper's Fig. 4 grid: every (axis, dtype, dim, fence) GEMV point of
  the speedup figure plus its host-only baseline.
"""
from __future__ import annotations

from repro.core.timing import DEFAULT_SYSTEM, LpddrTimings, PimSpec, SystemSpec
from .executor import GemvRequest, PimResult
from .tileconfig import ALL_DTYPES, PimDType

GOLDEN_SPECS = {
    "lp5x-9600": DEFAULT_SYSTEM,
    "rcd24-mac2": SystemSpec(timings=LpddrTimings(tRCD=24.0),
                             pim=PimSpec(mac_interval_ck=2)),
}
GOLDEN_SHAPES = [("pim", 256, 1024, PimDType.W8A8, False, False),
                 ("pim", 512, 2048, PimDType.W8A16, True, False),
                 ("pim", 1024, 512, PimDType.W4A8, False, True),
                 ("base", 1024, 1024, PimDType.W8A8, False, False)]


def golden_requests() -> list[tuple[str, GemvRequest]]:
    """``(label, request)`` for every point of the golden grid."""
    return [(f"{sname}/{kind}-{h}x{w}-{dt.name}"
             + ("-fence" if f else "") + ("-reshape" if r else ""),
             GemvRequest.pim(h, w, dt, fence=f, reshape=r, spec=sp)
             if kind == "pim" else GemvRequest.baseline(h, w, dt, spec=sp))
            for sname, sp in GOLDEN_SPECS.items()
            for (kind, h, w, dt, f, r) in GOLDEN_SHAPES]


def golden_record(res: PimResult) -> dict:
    """The fields of one result that the golden fixture pins."""
    return dict(cycles=res.cycles, ns=res.ns, flops=res.flops,
                weight_bytes=res.weight_bytes,
                utilization=res.utilization, split=res.split,
                counts=[int(c) for c in res.counts],
                energy=res.energy)


FIG4_DIMS = [512, 1024, 2048, 4096, 8192]
FIG4_BASE = 4096


def fig4_grid(dims=None, dtypes=ALL_DTYPES) -> list[GemvRequest]:
    """Every (axis, dtype, dim, fence) point of Fig. 4 + its baseline."""
    reqs: list[GemvRequest] = []
    seen: set = set()
    for fence in (False, True):
        for axis in ("activation", "output"):
            for dt in dtypes:
                for d in dims or FIG4_DIMS:
                    H, W = ((FIG4_BASE, d) if axis == "activation"
                            else (d, FIG4_BASE))
                    for r in (GemvRequest.pim(H, W, dt, fence=fence),
                              GemvRequest.baseline(H, W, dt)):
                        if r.key not in seen:
                            seen.add(r.key)
                            reqs.append(r)
    return reqs
