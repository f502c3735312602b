"""A serving configuration and mixes small enough for a CPU test run, and
helpers that drive the rest of a run without the chip."""
import copy

import jax

TINY = dict(name="tiny", arch="decoder", source="test", hidden_size=64,
            intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=500, hidden_act="silu", rms_norm_eps=1e-5,
            rope_theta=10000.0, tie_word_embeddings=False,
            serve=dict(dtype="bfloat16", slots=4, max_seq=256,
                       planner_layers=2),
            check=dict(max_logit_gap=0.02))

# The same model, checked as the offline MoE cell is: on the 99th
# percentile of the served tokens' gaps.
TINY_P99 = dict(TINY, name="tiny-p99", check=dict(p99_logit_gap=0.02))

TINY_MOE = dict(copy.deepcopy(TINY), name="tiny-moe", num_local_experts=4,
                num_experts_per_tok=2, tie_word_embeddings=True)

OPEN = dict(kind="open_loop", rate_per_s=20.0, block=8,
            prompt=dict(median=24, sigma=0.8, min=8, max=64, multiple=8),
            output=dict(median=8, sigma=0.8, min=4, max=16), drain_s=20,
            check_min_tokens=10)

OFFLINE = dict(OPEN, kind="offline", backlog=6, block=8, blocks=8)

# A model whose decode GEMV shapes are small enough for a CPU sweep.
SWEEP_MODEL = dict(name="tiny-sweep", arch="decoder", hidden_size=256,
                   intermediate_size=128, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
                   vocab_size=500, num_local_experts=8,
                   num_experts_per_tok=2, rope_theta=1e4,
                   rms_norm_eps=1e-6)


class NoTrace:
    dir = None
    sampled = False

    def sample(self, seconds):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def clock():
    from run import Clock
    return Clock(jax.devices()[:1])


def serve(cfg=TINY, mix=OPEN, seed=7, seconds=2.0, control=False):
    import serve_driver
    return serve_driver.run({"name": "test"}, cfg, mix, seed, seconds,
                            NoTrace(), clock(), control=control)


def sweep(seed=7, seconds=0.5, control=False):
    import registry
    import sweep_driver
    return sweep_driver.run({"name": "test"}, SWEEP_MODEL,
                            registry.traffic("sitesweep"), seed, seconds,
                            NoTrace(), clock(), control=control)
