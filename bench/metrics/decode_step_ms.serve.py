"""Median host milliseconds of the window's ``step()`` calls that only
decoded (no admission)."""
import statistics


def read(run):
    t = [s["t1"] - s["t0"] for s in run.steps
         if not s["admit"] and s["batch"]]
    return 1e3 * statistics.median(t) if t else None
