import numpy as np

import loadgen
import registry


def _key(reqs):
    return [(r["arrival_s"], r["max_new"], r["prompt"].tobytes())
            for r in reqs]


def test_open_loop_same_seed_same_requests():
    mix = registry.traffic("chat")
    a = loadgen.open_loop(mix, 2 ** 40 + 11, 40, 49152)
    b = loadgen.open_loop(mix, 2 ** 40 + 11, 40, 49152)
    c = loadgen.open_loop(mix, 2 ** 40 + 12, 40, 49152)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_open_loop_every_seed_gets_the_same_work():
    mix = registry.traffic("chat")
    runs = [loadgen.open_loop(mix, s, 40, 49152) for s in (1, 2, 3 ** 30)]
    n = round(mix["rate_per_s"] * 40)
    for reqs in runs:
        assert len(reqs) == n
        assert max(r["arrival_s"] for r in reqs) < 40
    shapes = [sorted((len(r["prompt"]), r["max_new"]) for r in reqs)
              for reqs in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    # the same gaps in another order (each run leaves one unused)
    gaps = [np.round(np.diff([r["arrival_s"] for r in reqs]), 9)
            for reqs in runs]
    assert len(np.intersect1d(gaps[0], gaps[1])) >= n - 2
    assert not np.array_equal(gaps[0], gaps[1])
    lens = {len(r["prompt"]) for r in runs[0]}
    assert lens == set(loadgen.prompt_lengths(mix, n))


def test_open_loop_arrivals_hold_bursts():
    """The gaps come in any order, so some stretch of eight arrivals is
    far shorter or longer than eight mean gaps."""
    mix = registry.traffic("chat")
    seconds = 51
    n = loadgen.request_count(mix, seconds)
    mean = seconds / n
    worst = 0.0
    for seed in range(20):
        t = [r["arrival_s"] for r in loadgen.open_loop(mix, seed, seconds,
                                                       49152)]
        spans = [(t[i + 8] - t[i]) / (8 * mean) for i in range(n - 8)]
        worst = max(worst, max(abs(x - 1.0) for x in spans))
    assert worst > 0.5


def test_offline_blocks_hold_the_whole_distribution():
    mix = registry.traffic("batch")
    a = loadgen.offline(mix, 5, 49155, blocks=3)
    b = loadgen.offline(mix, 5, 49155, blocks=3)
    c = loadgen.offline(mix, 6, 49155, blocks=3)
    assert _key(a) == _key(b) and _key(a) != _key(c)
    blk = mix["block"]
    first = sorted((len(r["prompt"]), r["max_new"]) for r in a[:blk])
    for k in range(1, 3):
        assert first == sorted((len(r["prompt"]), r["max_new"])
                               for r in a[k * blk:(k + 1) * blk])
    assert max(len(r["prompt"]) for r in a) <= mix["prompt"]["max"]


def test_lognormal_quantiles_respect_bounds_and_multiples():
    spec = dict(median=256, sigma=0.8, min=64, max=1024, multiple=64)
    q = loadgen.lognormal_quantiles(spec, 200)
    assert q.min() >= 64 and q.max() <= 1024 and (q % 64 == 0).all()
    assert abs(np.median(q) - 256) <= 64


def test_sweep_scales_differ_per_sweep_and_seed():
    mix = registry.traffic("sitesweep")
    s0 = loadgen.sweep_scales(mix, 3, 0)
    assert s0 == loadgen.sweep_scales(mix, 3, 0)
    assert s0 != loadgen.sweep_scales(mix, 3, 1)
    assert s0 != loadgen.sweep_scales(mix, 4, 0)
    lo, hi = mix["scale_range"]
    assert all(lo <= v <= hi for v in s0.values())
