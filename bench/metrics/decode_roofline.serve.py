"""Decode steps' share of the memory roofline, in percent.

Least time: the bytes a decode step needs (``counts.decode_step_bytes``:
weights once, KV at the live positions, at bfloat16) over the chip's HBM
bandwidth, summed over the window's decode-only steps; divided by the
device time the trace shows inside those steps' host spans.
"""
from counts import decode_step_bytes


def read(run):
    spans = {int(s[2].get("i", -1)): s for s in run.trace.spans("bench.step")}
    steps = [s for s in run.steps
             if not s["admit"] and s["decode_ctx"] and s["i"] in spans]
    if not steps:
        return None
    dev = run.trace.span_device_s([spans[s["i"]] for s in steps]).sum()
    if dev <= 0:
        return None
    least = sum(decode_step_bytes(run.config, s["decode_ctx"])
                for s in steps) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / dev
