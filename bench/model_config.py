"""A model configuration file (``bench/configs/<name>.json``) as the
program's ``ArchConfig``, and the shapes the benchmark counts with.

The file holds the published config.json keys as they are run; keys that
differ from the source are listed under ``reduced`` with their published
values under ``published``.  The program has no embedding, attention,
residual or logit multipliers, so a file may only state the values that
the program computes with (1, ``head_dim ** -0.5``, 1, 1); any other
value is refused here rather than silently not applied.
"""
from __future__ import annotations

import math


def padded_vocab(vocab: int) -> int:
    """The program pads its embedding and head to a multiple of 256."""
    return -(-vocab // 256) * 256


def dims(c: dict) -> dict:
    """The sizes the counters and the reference use."""
    e = c.get("num_local_experts", 0)
    return dict(
        L=c["num_hidden_layers"], d=c["hidden_size"],
        hq=c["num_attention_heads"], hkv=c["num_key_value_heads"],
        hd=c.get("head_dim",
                 c["hidden_size"] // c["num_attention_heads"]),
        ff=c["intermediate_size"], V=padded_vocab(c["vocab_size"]),
        vocab=c["vocab_size"], E=e,
        k=c.get("num_experts_per_tok", 0),
        tied=bool(c.get("tie_word_embeddings", False)),
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


def check_multipliers(c: dict) -> None:
    hd = dims(c)["hd"]
    run = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "logits_scaling": 1.0, "attention_multiplier": hd ** -0.5}
    for key, value in run.items():
        if key in c and not math.isclose(c[key], value, rel_tol=1e-9):
            raise SystemExit(f"config states {key}={c[key]}, but the "
                             f"program computes with {value}")
    if c.get("hidden_act", "silu") != "silu":
        raise SystemExit("the serving cells run SwiGLU (hidden_act silu)")


def arch_config(c: dict, n_layers: int | None = None):
    """The program's ``ArchConfig`` for this file (``n_layers`` overrides
    the depth, e.g. for the planner of the whole model)."""
    from repro.configs.base import ArchConfig, MoeConfig

    check_multipliers(c)
    d = dims(c)
    moe = MoeConfig(n_experts=d["E"], top_k=d["k"]) if d["E"] else None
    return ArchConfig(
        name=c["name"], family="moe" if moe else "dense",
        n_layers=n_layers or d["L"], d_model=d["d"], n_heads=d["hq"],
        n_kv_heads=d["hkv"], d_head=d["hd"], d_ff=d["ff"],
        vocab=d["vocab"], mlp="swiglu", tie_embeddings=d["tied"],
        rope_theta=d["theta"], norm_eps=d["eps"], moe=moe,
        source=c.get("source", ""))


def gemv_shapes(c: dict) -> list[tuple[int, int]]:
    """Distinct (rows, columns) of the weight GEMVs of one decode token:
    attention projections, router and experts (or MLP), and the head."""
    d = dims(c)
    shapes = [(d["hq"] * d["hd"], d["d"]), (d["hkv"] * d["hd"], d["d"]),
              (d["d"], d["hq"] * d["hd"])]
    if d["E"]:
        shapes.append((d["E"], d["d"]))
    shapes += [(d["ff"], d["d"]), (d["d"], d["ff"]), (d["V"], d["d"])]
    return list(dict.fromkeys(shapes))
