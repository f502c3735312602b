"""Median microseconds of the offload planner's query per decode step:
program span ``serve.plan`` (``OffloadController.observe``)."""
import statistics

from spans import in_window


def read(run):
    t = [r.t1_ns - r.t0_ns for r in in_window(run, "serve.plan")]
    return statistics.median(t) / 1e3 if t else None
