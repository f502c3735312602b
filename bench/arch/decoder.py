"""The llama / GraniteMoE decoder: every layer attention (GQA with rotary
embedding) and a SwiGLU MLP or a router over SwiGLU experts.

Everything the benchmark knows of this architecture is here, behind the
interface that ``registry.arch`` hands out:

* ``arch_config(c, n_layers=None)``: the program's ``ArchConfig``;
* ``gemv_shapes(c)``: the weight GEMVs of one decode token (the sweep);
* ``vocab(c)``: the token ids the traffic may draw;
* ``schema(c)``: the weight leaves that ``weights.make_params`` draws;
* ``logits(params, c, tokens, length, quant=None)``: the plain reference;
* ``decode_weight_bytes(c, batch)``, ``state_bytes(c, context)``,
  ``token_flops(c, context, logits)``, ``param_bytes(c)``: the counts.

The configuration file holds the published config.json keys as they are
run; keys that differ from the source are listed under ``reduced`` with
their published values under ``published``.  The program has no
embedding, attention, residual or logit multipliers, so a file may only
state the values that the program computes with (1, ``head_dim ** -0.5``,
1, 1); any other value is refused here rather than silently not applied.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from counts import BYTES
from reference.model_ref import HI, _mm, _norm
from weights import padded_vocab


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


def vocab(c: dict) -> int:
    return dims(c)["vocab"]


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


# -- weights ----------------------------------------------------------------

def _layer_schema(d: dict) -> dict:
    dm, hq, hkv, hd, ff = d["d"], d["hq"], d["hkv"], d["hd"], d["ff"]
    s = {"ln1": ((dm,), None), "ln2": ((dm,), None),
         "attn/wq": ((dm, hq * hd), 1 / math.sqrt(dm)),
         "attn/wk": ((dm, hkv * hd), 1 / math.sqrt(dm)),
         "attn/wv": ((dm, hkv * hd), 1 / math.sqrt(dm)),
         "attn/wo": ((hq * hd, dm), 1 / math.sqrt(hq * hd))}
    if d["E"]:
        e = d["E"]
        s.update({"moe/router": ((dm, e), 1 / math.sqrt(dm)),
                  "moe/wi": ((e, dm, ff), 1 / math.sqrt(dm)),
                  "moe/wg": ((e, dm, ff), 1 / math.sqrt(dm)),
                  "moe/wo": ((e, ff, dm), 1 / math.sqrt(ff))})
    else:
        s.update({"mlp/wi": ((dm, ff), 1 / math.sqrt(dm)),
                  "mlp/wg": ((dm, ff), 1 / math.sqrt(dm)),
                  "mlp/wo": ((ff, dm), 1 / math.sqrt(ff))})
    return s


def schema(c: dict) -> dict:
    """The program's parameter tree: the top-level leaves, and one stack
    of ``L`` identical layers under ``blocks``."""
    d = dims(c)
    top = {"embed": ((d["V"], d["d"]), 0.02), "ln_f": ((d["d"],), None)}
    if not d["tied"]:
        top["lm_head"] = ((d["d"], d["V"]), 0.02)
    return dict(top=top, stacks={"blocks": (d["L"], _layer_schema(d))})


# -- the plain reference ----------------------------------------------------
#
# RMSNorm (weight ``1 + gamma``), rotary embedding on the two halves of
# each head, grouped-query causal attention, SwiGLU, or a router whose
# softmax's top-k, renormalised, weighs the experts' SwiGLU outputs.  The
# head is the embedding's transpose where the configuration ties them.

def _rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, wg, wi, wo, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wi, quant), wo,
               quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _layer(p, x, dm, quant):
    hq, hkv, hd, theta, eps, k = dm
    s = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    a = p["attn"]
    q = _rope(_mm(h, a["wq"], quant).reshape(s, hq, hd), theta)
    kk = _rope(_mm(h, a["wk"], quant).reshape(s, hkv, hd), theta)
    v = _mm(h, a["wv"], quant).reshape(s, hkv, hd)
    rep = hq // hkv
    kk = jnp.repeat(kk, rep, axis=1)          # query head i -> kv i // rep
    v = jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, kk, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v, precision=HI)
    x = x + _mm(o.reshape(s, hq * hd), a["wo"], quant)
    h = _norm(x, p["ln2"], eps)
    if "moe" in p:
        m = p["moe"]
        probs = jax.nn.softmax(_mm(h, m["router"], quant), -1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / jnp.sum(top, -1, keepdims=True)
        e = probs.shape[-1]
        gate = jnp.zeros((s, e)).at[jnp.arange(s)[:, None], idx].set(top)

        def expert(carry, w):
            y = _swiglu(h, w[0], w[1], w[2], quant)
            return carry, y

        _, ys = jax.lax.scan(expert, None, (m["wg"], m["wi"], m["wo"]))
        y = jnp.einsum("se,esd->sd", gate, ys, precision=HI)
    else:
        m = p["mlp"]
        y = _swiglu(h, m["wg"], m["wi"], m["wo"], quant)
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, eps, quant):
    return _mm(_norm(x, ln_f, eps), head, quant)


def logits(params, c: dict, tokens: np.ndarray, length: int,
           quant: str | None = None) -> jnp.ndarray:
    """Logits ``(len(tokens), V)`` of one sequence, padded to ``length``
    positions for the compile (causal attention: padding comes after),
    one layer at a time."""
    d = dims(c)
    n = len(tokens)
    ids = np.zeros(length, np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    dm = (d["hq"], d["hkv"], d["hd"], d["theta"], d["eps"], d["k"])
    for layer in range(d["L"]):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        x = _layer(p, x, dm, quant)
    head = params["embed"].T if d["tied"] else params["lm_head"]
    return _head(x, params["ln_f"], head, d["eps"], quant)[:n]


# -- counts (bench/counts.py says how they are counted) ---------------------

def _attn_params(d: dict) -> int:
    return d["d"] * d["hd"] * (2 * d["hq"] + 2 * d["hkv"])


def _ffn_params_per_token(d: dict) -> int:
    if d["E"]:
        return d["d"] * d["E"] + d["k"] * 3 * d["d"] * d["ff"]
    return 3 * d["d"] * d["ff"]


def experts_reached(d: dict, batch: int) -> float:
    """Expected experts that ``batch`` tokens reach with ``k`` of ``E``
    each, under uniform routing."""
    e, k = d["E"], d["k"]
    return e * (1.0 - (1.0 - k / e) ** batch)


def decode_weight_bytes(c: dict, batch: int) -> float:
    d = dims(c)
    per_layer = _attn_params(d) + 2 * d["d"]
    if d["E"]:
        per_layer += d["d"] * d["E"]
        per_layer += experts_reached(d, batch) * 3 * d["d"] * d["ff"]
    else:
        per_layer += 3 * d["d"] * d["ff"]
    head = d["d"] * d["V"]
    gathered = batch * d["d"]
    return BYTES * (d["L"] * per_layer + head + d["d"] + gathered)


def state_bytes(c: dict, context: int) -> float:
    """Keys and values of one slot at ``context`` live positions."""
    d = dims(c)
    return BYTES * 2 * d["L"] * d["hkv"] * d["hd"] * context


def token_flops(c: dict, context: int, logits: bool) -> float:
    """Model FLOPs of one token at position ``context - 1``."""
    d = dims(c)
    lin = d["L"] * (_attn_params(d) + _ffn_params_per_token(d))
    if logits:
        lin += d["d"] * d["V"]
    attn = d["L"] * 2 * 2 * context * d["hq"] * d["hd"]
    return 2.0 * lin + attn


def param_bytes(c: dict) -> float:
    d = dims(c)
    per_layer = _attn_params(d) + 2 * d["d"]
    if d["E"]:
        per_layer += d["d"] * d["E"] + d["E"] * 3 * d["d"] * d["ff"]
    else:
        per_layer += 3 * d["d"] * d["ff"]
    top = d["V"] * d["d"] * (1 if d["tied"] else 2) + d["d"]
    return BYTES * (d["L"] * per_layer + top)
