"""Median milliseconds of one admission: program span ``serve.admit``
(prefill dispatch, cache merge, and the host read of the first token,
which waits for the prefill)."""
import statistics

from spans import in_window


def read(run):
    t = [r.t1_ns - r.t0_ns for r in in_window(run, "serve.admit")]
    return statistics.median(t) / 1e6 if t else None
