"""Milliseconds a sweep spends preparing lanes for the resolver: program
spans ``sim.lookup`` (dedupe, hashing, lane LRU) and ``sim.pack`` (per
slab: row packing, configs, ``device_put``), summed per sweep, median
over the recorded sweeps."""
import statistics

from spans import per_sweep_ns


def read(run):
    t = per_sweep_ns(run, "sim.lookup", "sim.pack")
    return statistics.median(t) / 1e6 if t else None
