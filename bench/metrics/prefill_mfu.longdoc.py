"""Prefill's share of the chip's bf16 peak, in percent.

The model FLOPs of the prompts admitted in the traced sample
(``counts.prefill_flops``: every weight matrix a prompt token passes,
the top-k experts' held share, the SSM update, causal attention; logits
for the last token) over the device seconds of their ``jit_prefill``
modules times the bf16 peak.  A step counts when its ``bench.step`` span
holds the start of one ``jit_prefill`` module per prompt it admitted
(the profiler kept the step's prefills); the modules' device time is
taken from the first device.
"""
import numpy as np

from counts import prefill_flops


def read(run):
    spans = {int(s[2].get("i", -1)): s for s in run.trace.spans("bench.step")}
    mods = sorted((s, d) for dev in run.trace.devices[:1]
                  for name, s, d in dev["modules"] if "jit_prefill" in name)
    starts = np.asarray([s for s, _ in mods], np.float64)
    flops = secs = 0.0
    for s in run.steps:
        if not s["prefill"] or s["i"] not in spans:
            continue
        a, b = spans[s["i"]][:2]
        lo, hi = np.searchsorted(starts, [a, b], side="left")
        if hi - lo != len(s["prefill"]):
            continue
        flops += sum(prefill_flops(run.config, p) for p in s["prefill"])
        secs += sum(d for _, d in mods[lo:hi]) / 1e9
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
