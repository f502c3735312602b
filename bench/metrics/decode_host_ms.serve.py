"""Median host milliseconds of a decode-only step in which the device
ran nothing: over the traced window's ``serve.step`` spans that hold a
``serve.decode``, no ``serve.admit``, and the start of a
``jit_decode_step`` module (the profiler kept the step's device events),
the span's length less the device busy time inside it.  The program's
spans are on the profiler's host plane, on the device trace's clock."""
import statistics

import numpy as np


def _sorted(starts):
    return np.sort(np.asarray(list(starts), np.float64))


def _holds(starts, a, b) -> bool:
    i = np.searchsorted(starts, a, side="left")
    return i < starts.size and starts[i] <= b


def read(run):
    a, b = run.window_ns
    dec, adm = (_sorted(s[0] for s in run.trace.spans(name))
                for name in ("serve.decode", "serve.admit"))
    mod = _sorted(s for dev in run.trace.devices[:1]
                  for name, s, _ in dev["modules"]
                  if "jit_decode_step" in name)
    steps = [s for s in run.trace.spans("serve.step")
             if a <= s[0] and s[1] <= b and _holds(dec, s[0], s[1])
             and _holds(mod, s[0], s[1]) and not _holds(adm, s[0], s[1])]
    if not steps:
        return None
    busy = run.trace.span_device_s(steps)
    return 1e3 * statistics.median(
        (s[1] - s[0]) / 1e9 - d for s, d in zip(steps, busy))
