"""The one traffic generator: reads a mix's parameter file, draws from the seed.

Every seed gets the same work.  Prompt lengths, output lengths and gaps
between arrivals are fixed quantiles of the distributions the mix names,
so two seeds send the same multiset of requests and differ only in their
order, their token ids and (open loop) the order of the gaps.  That keeps
the spread between seeds to what the system does with the work, not to
how much work a seed happened to draw.

Kinds of mix (``"kind"`` in ``bench/traffic/<mix>.json``):

* ``open_loop``: requests arrive on a schedule at ``rate_per_s``,
  whatever the server does; ``round(rate * seconds)`` of them are due in
  the window.  The gaps are the quantiles of an exponential in an order
  drawn from the seed alone, so the arrivals are a Poisson process's,
  bursts included; the lengths are dealt into blocks of ``block``.
* ``offline``: a backlog that never empties; requests come in blocks of
  ``block`` that each hold the whole length distribution.
* ``design_sweep``: simulator requests, one sweep per design point; each
  sweep scales the named core timings of every device family by a factor
  drawn from ``scale_range``.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

# The pairing of prompt and output lengths is part of the mix, not of the
# seed: one fixed permutation for every run.
_PAIRING_SEED = 20260417


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed (any size of seed)."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random`` derived from any run seed."""
    return int(rng(seed, 0).integers(0, 2 ** 31 - 1))


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths: the (i + 1/2)/n quantiles of a lognormal
    with ``median`` and ``sigma``, clipped to ``[min, max]`` and rounded
    up to a multiple of ``multiple`` (default 1)."""
    nd = NormalDist()
    mult = int(spec.get("multiple", 1))
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"]
                                      * nd.inv_cdf((i + 0.5) / n))
        x = min(max(x, spec["min"]), spec["max"])
        out.append(int(math.ceil(x / mult) * mult))
    return np.asarray(out, dtype=np.int64)


def length_pairs(mix: dict, n: int) -> list[tuple[int, int]]:
    """``n`` (prompt, output) length pairs: the same multiset for every
    seed."""
    p = lognormal_quantiles(mix["prompt"], n)
    o = lognormal_quantiles(mix["output"], n)
    o = o[np.random.default_rng(_PAIRING_SEED).permutation(n)]
    return [(int(a), int(b)) for a, b in zip(p, o)]


def request_count(mix: dict, seconds: float,
                  rate: float | None = None) -> int:
    """How many distinct length pairs a run of the mix draws from: the
    requests due in the window (open loop) or one block (offline)."""
    if mix["kind"] == "open_loop":
        rate = float(rate if rate is not None else mix["rate_per_s"])
        return max(1, int(round(rate * seconds)))
    return int(mix["block"])


def prompt_lengths(mix: dict, n: int) -> list[int]:
    """Every prompt length that ``n`` stratified requests of the mix
    send (the shapes to warm up, and no others)."""
    return sorted({p for p, _ in length_pairs(mix, n)})


def _tokens(g: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return g.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)


def _dealt(values: list, blocks: int, g: np.random.Generator) -> list:
    """``values`` dealt round-robin, largest first, into ``blocks``
    consecutive blocks of near-equal make-up, each in a seeded order."""
    order = sorted(range(len(values)), key=lambda i: values[i],
                   reverse=True)
    out = []
    for b in range(blocks):
        part = [values[i] for i in order[b::blocks]]
        out += [part[j] for j in g.permutation(len(part))]
    return out


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              rate: float | None = None) -> list[dict]:
    """Requests due in a window of ``seconds``: ``arrival_s`` from the
    window's start, prompt tokens, ``max_new``.  The gaps between
    arrivals come in a uniformly random order, so any stretch of the
    window may hold a burst; the length pairs are dealt into blocks of
    ``block`` that each hold a near-equal share of the lengths, each
    block in the seed's order."""
    rate = float(rate if rate is not None else mix["rate_per_s"])
    n = request_count(mix, seconds, rate)
    blocks = max(1, n // int(mix["block"]))
    pairs = length_pairs(mix, n)
    g = rng(seed, 1)
    gaps = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = gaps[g.permutation(n)] * (n / rate) / gaps.sum()
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    pairs = _dealt(pairs, blocks, g)
    out = []
    for i, (plen, olen) in enumerate(pairs):
        out.append(dict(arrival_s=float(arrivals[i]), max_new=olen,
                        prompt=_tokens(g, plen, vocab)))
    return out


def offline(mix: dict, seed: int, vocab: int, blocks: int) -> list[dict]:
    """``blocks`` blocks of requests; each block holds every stratified
    length pair of the mix once, in its own seeded order."""
    b = int(mix["block"])
    pairs = length_pairs(mix, b)
    g = rng(seed, 2)
    out = []
    for _ in range(blocks):
        for j in g.permutation(b):
            plen, olen = pairs[j]
            out.append(dict(arrival_s=0.0, max_new=olen,
                            prompt=_tokens(g, plen, vocab)))
    return out


def sweep_scales(mix: dict, seed: int, sweep: int) -> dict[str, float]:
    """The timing factor of each device family in sweep ``sweep``."""
    lo, hi = mix["scale_range"]
    g = rng(seed, 1000 + sweep)
    return {name: float(g.uniform(lo, hi)) for name in mix["families"]}
