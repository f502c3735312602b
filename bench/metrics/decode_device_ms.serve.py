"""Median device milliseconds of one decode step: the events of the XLA
module ``jit_decode_step`` (the jitted ``models/model.py`` decode step)
that start in the traced window."""
import statistics


def read(run):
    a, b = run.window_ns
    d = [dur for dev in run.trace.devices for name, s, dur in dev["modules"]
         if "jit_decode_step" in name and a <= s <= b]
    return statistics.median(d) / 1e6 if d else None
