"""Program spans of a traced run, for the per-layer readers.

While the profiler traces a run, the program records a span at each
layer boundary of the serving engine and of the simulator's resolve path
(``repro.obs``: name, ``perf_counter_ns`` ends, parent, counts), the same
clock as the harness's step, sweep and request records.  A span tree is
recorded whole once its root opens under the profiler, so a sampled
sweep is covered to its end.  A program without the recorder gives no
spans, and every reader of them then returns None.
"""
from __future__ import annotations


def records(run) -> list:
    """The run's program spans, taken from the recorder once and kept
    on ``run`` for the other readers."""
    spans = getattr(run, "spans", None)
    if spans is None:
        try:
            from repro import obs
        except ImportError:
            spans = []
        else:
            spans = obs.take()
        run.spans = spans
    return spans


def _ns(t: float) -> int:
    return int(round(t * 1e9))


def inside(run, names, a_ns: int, b_ns: int) -> list:
    """Spans named in ``names`` that start and end inside [a_ns, b_ns]."""
    return [r for r in records(run) if r.name in names
            and a_ns <= r.t0_ns and r.t1_ns <= b_ns]


def in_window(run, *names) -> list:
    """Spans named in ``names`` inside the run's window (host clock)."""
    a, b = run.window
    return inside(run, names, _ns(a), _ns(b))


def per_sweep_ns(run, *names) -> list[int]:
    """Summed nanoseconds of the spans named in ``names``, one sum per
    window sweep whose ``sim.run_many`` was recorded."""
    out = []
    for s in run.sweeps:
        a, b = _ns(s["t0"]), _ns(s["t1"])
        if inside(run, ("sim.run_many",), a, b):
            out.append(sum(r.t1_ns - r.t0_ns
                           for r in inside(run, names, a, b)))
    return out
