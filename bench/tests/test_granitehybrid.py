"""granite-4.0-h-small, ``bench/arch/granitehybrid.py``: the configuration
file against the published config.json, the reference's expert
share, the counts at the cut, and a tiny hybrid run as a serving cell
and checked against the reference."""
import math
import types

import jax
import numpy as np
import pytest

import counts
import registry
import tiny
from registry import metric_reader
from test_span_readers import MS, _ev, _trace
from weights import make_params

# A GraniteMoeHybrid at a CPU size: Mamba-2 and NoPE attention layers in
# the published order's first four, 4 of 8 experts held (ids 2..5) plus a
# shared expert, the four multipliers; prompts span several 16-position
# chunks.
TINY_HYBRID = dict(
    name="tiny-hybrid", arch="granitehybrid", source="test",
    hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba",
                 "attention"],
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=16, mamba_n_groups=1,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    num_local_experts=4, expert_first=2, num_experts_per_tok=3,
    published=dict(num_local_experts=8, num_hidden_layers=6),
    vocab_size=500, hidden_act="silu", rms_norm_eps=1e-5,
    rope_theta=10000.0, position_embedding_type="nope",
    tie_word_embeddings=True, embedding_multiplier=12.0,
    attention_multiplier=0.125, residual_multiplier=0.22,
    logits_scaling=4.0,
    serve=dict(dtype="bfloat16", slots=4, max_seq=256, planner_layers=6),
    check=dict(p99_logit_gap=0.02))

# The published config.json (ibm-granite/granite-4.0-h-small), but for
# layer_types (attention at 5, 15, 25, 35 of 40) and the two cut keys.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_key_value_heads": 8,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352
}


def _cfg():
    return registry.config(registry.load_benchmark(), "granite-4.0-h-small")


def test_config_is_the_published_one_but_for_the_cut():
    c = _cfg()
    entry = next(e for e in registry.load_benchmark()["configs"]
                 if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers",
                                                "num_local_experts"]
    assert c["published"] == {"num_hidden_layers": 40,
                              "num_local_experts": 72}
    assert (c["num_hidden_layers"], c["num_local_experts"]) == (10, 9)
    assert c["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert [i for i, t in enumerate(c["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert len(c["layer_types"]) == 40
    assert {k: c[k] for k in PUBLISHED} == PUBLISHED


@pytest.mark.parametrize("arch", [None, "no-such-arch"])
def test_a_config_without_an_arch_module_names_both(arch):
    c = {k: v for k, v in TINY_HYBRID.items() if k != "arch"}
    if arch is not None:
        c["arch"] = arch
    with pytest.raises(SystemExit, match=r"bench/arch/ has "
                       r"\['decoder', 'granitehybrid'\]"):
        registry.arch(c)


def test_counts_at_the_cut():
    """4.01 GB of bf16 layers and 0.82 GB of tied embedding; per slot
    4.19 MB of f32 SSM state per Mamba layer (counted at 2 bytes, read and
    written) and the K/V of one attention layer."""
    c = _cfg()
    a = registry.arch(c)
    d = a.dims(c)
    emb = 2 * d["V"] * d["d"]
    assert round((a.param_bytes(c) - emb) / 1e9, 2) == 4.01
    assert round(emb / 1e9, 2) == 0.82
    assert d["nh"] * d["p"] * d["N"] * 4 == 4194304
    kv = 2 * 2 * 8 * 128
    rec = 2 * 2 * 9 * (128 * 64 * 128 + 3 * (8192 + 256))
    assert a.state_bytes(c, 1000) == kv * 1000 + rec
    # weights a decode step reads: at batch 1, 10 of 72 experts, so
    # 1.25 of the 9 held; at 16 nearly all 9
    one = a.decode_weight_bytes(c, 1)
    full = a.decode_weight_bytes(c, 10 ** 6)
    expert = 2 * 3 * 4096 * 768 * 10
    assert math.isclose(full - one, expert * (9 - 9 * 10 / 72)
                        + 2 * (10 ** 6 - 1) * 4096)
    assert a.gemv_shapes(c) == [(16768, 4096), (4096, 8192), (4096, 4096),
                                (1024, 4096), (72, 4096), (768, 4096),
                                (4096, 768), (1536, 4096), (4096, 1536),
                                (100352, 4096)]
    # model FLOPs of a prompt token: 2.54 G of weights (the experts at
    # top-10 of 72 times the 9 held), 57 M of SSM update, attention
    per = counts.prefill_flops(c, 4096) / 4096
    assert 2.6e9 < per < 2.7e9


def test_reference_shares_sum_to_the_uncut_layer():
    """The reference's MoE given each of 8 shares of the experts: the
    routed parts of all shares plus the shared expert once are the uncut
    layer's output."""
    c = dict(TINY_HYBRID, num_local_experts=8, expert_first=0)
    a = registry.arch(c)
    m = jax.tree.map(lambda x: x[0], make_params(
        c, 3, dtype=jax.numpy.float32)["mamba_blocks"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(1), (11, c["hidden_size"]))
    routed, shared = a.moe(m, h, 3, 0)
    parts = sum(a.moe(dict(m, **{w: m[w][f:f + 1]
                                 for w in ("wi", "wg", "wo")}),
                      h, 3, f)[0] for f in range(8))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(routed + shared),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5])
def test_tiny_hybrid_serves_correct(seed):
    res = tiny.serve(cfg=TINY_HYBRID, mix=tiny.OFFLINE, seed=seed)
    assert res["correct"], res["checks"]
    assert res["e2e"]["tokens_per_s"] > 0


def test_prefill_mfu_counts_only_steps_whose_prefills_were_kept():
    def step(i, start, dur):
        stats = types.SimpleNamespace(stats=[("i", i)])
        return ("bench.step", start, dur, stats)

    c = TINY_HYBRID
    host = [_ev("bench.window", 0, 100 * MS), step(0, 10 * MS, 10 * MS),
            step(1, 30 * MS, 10 * MS), step(2, 50 * MS, 10 * MS)]
    ops = [("fusion.1", 11 * MS, 8 * MS), ("fusion.2", 51 * MS, 8 * MS)]
    modules = [("jit_prefill(3)", 11 * MS, 3 * MS),      # step 0: two
               ("jit_prefill(4)", 15 * MS, 4 * MS),      # prompts kept
               ("jit_prefill(3)", 31 * MS, 2 * MS),      # step 1: one
               ("jit_decode_step(17)", 52 * MS, 5 * MS)]  # of two kept
    steps = [dict(i=0, prefill=[48, 64]), dict(i=1, prefill=[48, 32]),
             dict(i=2, prefill=[])]
    run = types.SimpleNamespace(trace=_trace(modules, host, ops),
                                window_ns=(0, 100 * MS), steps=steps,
                                config=c, peaks=dict(bf16_flops=1e12))
    flops = counts.prefill_flops(c, 48) + counts.prefill_flops(c, 64)
    assert metric_reader("prefill_mfu.longdoc")(run) == \
        pytest.approx(100 * flops / (0.007 * 1e12))
    run.steps = steps[1:]
    assert metric_reader("prefill_mfu.longdoc")(run) is None
