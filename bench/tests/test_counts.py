import json
import os

import pytest

import counts
import registry

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    return registry.config(registry.load_benchmark(), name)


def test_granite8b_weights_by_hand():
    c = _cfg("granite-8b")
    a = registry.arch(c)
    # per layer: q,o 4096x4096, k,v 4096x1024, SwiGLU 3 x 4096x14336,
    # two norms; 18 layers; embedding and head 49152 x 4096 each; ln_f
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 \
        + 2 * 4096
    params = 18 * per_layer + 2 * 49152 * 4096 + 4096
    assert params == 4_328_673_280
    assert a.param_bytes(c) == 2 * params               # 8.66 GB
    # a decode step reads everything but the embedding table, whose
    # batch rows it gathers instead
    step = 2 * (18 * per_layer + 49152 * 4096 + 4096 + 8 * 4096)
    assert a.decode_weight_bytes(c, 8) == step
    # KV at bf16: 18 layers x 8 kv heads x 128 x (k, v) x 2 B a position
    assert a.state_bytes(c, 100) == 100 * 18 * 8 * 128 * 2 * 2
    assert counts.decode_step_bytes(c, [100, 200]) == \
        a.decode_weight_bytes(c, 2) + a.state_bytes(c, 300)


def test_granite8b_token_flops_by_hand():
    c = _cfg("granite-8b")
    lin = 18 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    head = 4096 * 49152
    attn = 18 * 2 * 2 * 10 * 32 * 128
    assert counts.token_flops(c, 10, True) == 2 * (lin + head) + attn
    assert counts.token_flops(c, 10, False) == 2 * lin + attn
    assert counts.prefill_flops(c, 3) == (
        sum(counts.token_flops(c, n, False) for n in (1, 2, 3))
        + 2 * head)


def test_moe_counts_only_routed_experts():
    c = _cfg("granite-moe-3b-a800m")
    a = registry.arch(c)
    e, k, d, ff = 40, 8, 1536, 512
    one = a.decode_weight_bytes(c, 1)
    eight = a.decode_weight_bytes(c, 8)
    per_expert = 2 * 32 * 3 * d * ff
    assert eight - one == pytest.approx(
        per_expert * e * ((1 - k / e) - (1 - k / e) ** 8)
        + 2 * 7 * d)
    # the tied embedding is counted once, as the head
    assert a.param_bytes(c) == 2 * (
        32 * (d * 64 * (2 * 24 + 2 * 8) + 2 * d + d * e + e * 3 * d * ff)
        + 49408 * d + d)


def test_one_sweep_commands():
    import repro.core  # noqa: F401
    from repro.pimkernel.executor import PimExecutor
    import loadgen
    import sweep_driver

    c = _cfg("granite-moe-3b-a800m")
    mix = registry.traffic("sitesweep")
    point = sweep_driver.design_point(mix, loadgen.sweep_scales(mix, 1, 0))
    reqs, _ = sweep_driver.requests(c, mix, point)
    assert len(reqs) == 40          # 4 families x 5 shapes x PIM/baseline
    n = counts.sweep_counts(PimExecutor().plan_many(reqs))
    assert n["simulated"] == 11_110_280
    assert n["resolved"] == 3_439_480 and n["lanes"] == 39


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 7])
def test_sweep_weight_commands_match_the_planner(seed):
    """The independent count agrees with every request the planner
    makes for a sweep (the opcode histogram of its channel streams)."""
    import numpy as np

    import repro.core  # noqa: F401
    from repro.pimkernel.executor import PimExecutor
    import loadgen
    import sweep_driver

    c = _cfg("granite-moe-3b-a800m")
    mix = registry.traffic("sitesweep")
    point = sweep_driver.design_point(mix, loadgen.sweep_scales(mix, seed,
                                                                0))
    reqs, meta = sweep_driver.requests(c, mix, point)
    for (name, h, w, kind), r, p in zip(meta, reqs,
                                        PimExecutor().plan_many(reqs)):
        hist = sum(np.bincount(s[:, 0], minlength=17) for s in p.streams)
        want = counts.gemv_weight_commands(kind, h, w, mix["dtype"],
                                           point[name], reshape=r.reshape)
        assert {op: int(hist[op]) for op in want} == want, (name, h, w)


def test_weight_commands_match_the_golden_file():
    """The golden file's opcode counts, for its default memory system,
    against the independent count (other types, fences, reshape)."""
    import dataclasses

    import repro.core  # noqa: F401
    from repro.core.timing import SystemSpec
    from reference import pim_ref  # noqa: F401

    with open(os.path.join(BENCH, "reference", "golden_fleet.json")) as f:
        golden = json.load(f)
    fam = dataclasses.asdict(SystemSpec())
    cases = {"pim-256x1024-W8A8": ("pim", 256, 1024, "W8A8", False),
             "pim-512x2048-W8A16-fence": ("pim", 512, 2048, "W8A16", False),
             "pim-1024x512-W4A8-reshape": ("pim", 1024, 512, "W4A8", True),
             "base-1024x1024-W8A8": ("baseline", 1024, 1024, "W8A8", False)}
    for label, (kind, h, w, dt, reshape) in cases.items():
        want = counts.gemv_weight_commands(kind, h, w, dt, fam,
                                           reshape=reshape)
        got = golden["lp5x-9600/" + label]["counts"]
        assert {op: got[op] for op in want} == want, label
