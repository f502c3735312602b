"""Whole-step model FLOP utilisation, in percent: the model FLOPs of every
token the window's steps processed (prompts and outputs; top-k experts
only; causal attention at the live length; ``counts.py``) over the host
seconds of those ``step()`` calls times the chip's bf16 peak.  Each call
ends in a host read of its tokens, so its device work lies inside it.
Under an open loop the FLOPs of a window are set by the offered load;
the seconds the engine spends stepping are what a faster step cuts."""
from counts import prefill_flops, token_flops


def read(run):
    flops = 0.0
    secs = 0.0
    for s in run.steps:
        flops += sum(prefill_flops(run.config, p) for p in s["prefill"])
        flops += sum(token_flops(run.config, n, True)
                     for n in s["decode_ctx"])
        secs += s["t1"] - s["t0"]
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
