"""Operations and bytes the algorithm needs, from the configuration's shapes.

Kept with the benchmark so that every PR counts the same way.  Each
architecture's module (``bench/arch/<arch>.py``) counts its own layers by
these rules; the readers in ``bench/metrics/`` call the functions here,
which combine them.  Bytes are counted at the configuration's parameter
type (bfloat16, 2 bytes), also for the KV cache, whatever type the
program keeps it in.

* Weight bytes of one decode step: every matrix that a token multiplies
  against, read once for the whole batch (attention, MLP or the experts
  the batch routes to, router, head), the norms, and the embedding rows
  the batch gathers.  For a mixture of experts the experts needed are
  the expected number that ``B`` tokens reach with ``k`` of ``E``
  experts each, under uniform routing: ``E * (1 - (1 - k / E) ** B)``.
* State bytes of one decode step: keys and values at every live position
  of every active slot, and any fixed-size recurrent state of each.
* Model FLOPs of a token: 2 per multiply-add of every weight matrix it
  passes (only the top-k experts, the head only where logits are
  computed), plus causal attention at the live context length.
"""
from __future__ import annotations

import hashlib
import re

import registry
from reference.pim_ref import ACT, MAC, PRE, RD

BYTES = 2    # bfloat16


def decode_step_bytes(c: dict, contexts: list[int]) -> float:
    """Least bytes of one decode step whose active slots attend over
    ``contexts`` positions each: the weights, and each slot's state."""
    a = registry.arch(c)
    return (a.decode_weight_bytes(c, len(contexts))
            + sum(a.state_bytes(c, n) for n in contexts))


def token_flops(c: dict, context: int, logits: bool) -> float:
    """Model FLOPs of one token at position ``context - 1``."""
    return registry.arch(c).token_flops(c, context, logits)


def prefill_flops(c: dict, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, logits for the last one only."""
    a = registry.arch(c)
    head = a.token_flops(c, 1, True) - a.token_flops(c, 1, False)
    return sum(a.token_flops(c, p + 1, False) for p in range(prompt)) + head


# -- the simulator sweep ----------------------------------------------------

def sweep_counts(planned) -> dict:
    """Commands of one sweep: ``simulated`` over every channel stream of
    every request (the work asked for), ``resolved`` over the distinct
    (timing, stream) lanes the resolver has to run, unpadded."""
    simulated = 0
    lanes: dict = {}
    for p in planned:
        for s in p.streams:
            simulated += int(s.shape[0])
            key = (p.ctx.cyc, s.shape[0],
                   hashlib.blake2b(s.tobytes(), digest_size=16).digest())
            lanes[key] = int(s.shape[0])
    return dict(simulated=simulated, resolved=sum(lanes.values()),
                lanes=len(lanes))


def gemv_weight_commands(kind: str, h: int, w: int, dtype: str, fam: dict,
                         reshape: bool = False) -> dict[int, int]:
    """{opcode: count} of the commands that move one GEMV's weights,
    summed over channels, from the shape, the type and the family's
    numbers alone (paper §2.3, Fig. 3):

    * baseline: the weight bytes, split evenly over the channels, read
      sequentially: one RD per burst, one ACT and one PRE per page;
    * PIM: tiles of ``acc_regs`` rows by ``srf_bytes / a_bytes`` columns,
      logical blocks (h-tiles times the reshape split) dealt over the
      channel-first blocks in rounds; in a round every channel that holds
      a block issues, in lock step, one MAC per weight burst of a tile
      row-sweep for each chunk any block of the round uses.
    """
    w_bits, a_bits = (int(x) for x in re.fullmatch(
        r"W(\d+)A(\d+)", dtype.removeprefix("FP_")).groups())
    t, p = fam["timings"], fam["pim"]
    burst = t["channel_bits"] * t["burst_len"] // 8
    nch = fam["num_channels"]
    if kind == "baseline":
        per_ch = -(-h * w * w_bits // 8 // nch)
        pages = -(-per_ch // t["page_bytes"])
        return {RD: nch * -(-per_ch // burst), ACT: nch * pages,
                PRE: nch * pages}
    t_w = p["srf_bytes"] * 8 // a_bits
    t_h = p["acc_regs"]
    n_h, n_w = -(-h // t_h), -(-w // t_w)
    nblk = nch * fam["num_ranks"] * t["num_bankgroups"] \
        * t["banks_per_group"]
    split = 1
    if reshape and n_h < nblk and n_w > 1:
        split = min(p["max_reshape_split"], n_w, max(1, nblk // n_h))
    group_w = -(-n_w // split)
    n_log = n_h * split
    row_bytes = t_w * w_bits // 8
    macs = 0
    for rnd in range(-(-n_log // nblk)):
        blocks = range(rnd * nblk, min((rnd + 1) * nblk, n_log))
        channels = {(b % nblk) % nch for b in blocks}
        last_only = all(b // split == n_h - 1 for b in blocks)
        rows = h - (n_h - 1) * t_h if last_only else t_h
        groups = {b % split for b in blocks}
        chunks = sum(any(g * group_w + c < min((g + 1) * group_w, n_w)
                         for g in groups) for c in range(group_w))
        macs += len(channels) * chunks * -(-rows * row_bytes // burst)
    return {MAC: macs}
