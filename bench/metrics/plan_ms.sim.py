"""Milliseconds a sweep spends planning its streams: program span
``sim.plan`` (``PimExecutor.plan_many``), summed per sweep, median over
the recorded sweeps."""
import statistics

from spans import per_sweep_ns


def read(run):
    t = per_sweep_ns(run, "sim.plan")
    return statistics.median(t) / 1e6 if t else None
