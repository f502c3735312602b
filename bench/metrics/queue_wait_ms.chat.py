"""Median milliseconds a window request waited in the serving engine's
queue: program span ``serve.queue``, from ``submit`` to the start of its
admission."""
import statistics

from spans import in_window


def read(run):
    t = [r.t1_ns - r.t0_ns for r in in_window(run, "serve.queue")]
    return statistics.median(t) / 1e6 if t else None
