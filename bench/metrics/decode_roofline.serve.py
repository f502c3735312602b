"""Decode steps' share of the memory roofline, in percent.

Least time: the bytes a decode step needs (``counts.decode_step_bytes``:
weights once, KV at the live positions, at bfloat16) over the chip's HBM
bandwidth, summed over the traced window's decode-only steps whose device
events the profiler kept (the step's ``bench.step`` span holds the start
of a ``jit_decode_step`` module); divided by the device time the trace
shows inside those steps' spans.  A step whose events were dropped would
add its bytes and no time.
"""
import numpy as np

from counts import decode_step_bytes


def read(run):
    spans = {int(s[2].get("i", -1)): s for s in run.trace.spans("bench.step")}
    mod = np.sort(np.asarray([s for dev in run.trace.devices[:1]
                              for name, s, _ in dev["modules"]
                              if "jit_decode_step" in name], np.float64))

    def kept(span):
        k = np.searchsorted(mod, span[0], side="left")
        return k < mod.size and mod[k] <= span[1]

    steps = [s for s in run.steps
             if not s["admit"] and s["decode_ctx"] and s["i"] in spans
             and kept(spans[s["i"]])]
    if not steps:
        return None
    dev = run.trace.span_device_s([spans[s["i"]] for s in steps]).sum()
    if dev <= 0:
        return None
    least = sum(decode_step_bytes(run.config, s["decode_ctx"])
                for s in steps) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / dev
