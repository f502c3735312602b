"""Median host milliseconds of the window's ``step()`` calls in which the
engine admitted (prefilled) at least one request."""
import statistics


def read(run):
    t = [s["t1"] - s["t0"] for s in run.steps if s["admit"]]
    return 1e3 * statistics.median(t) if t else None
