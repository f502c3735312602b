"""GraniteMoeHybrid (granite-4.0-h): layers of two kinds in the order
``layer_types`` gives, Mamba-2 mixers and grouped-query attention with no
positional embedding, each followed by a router over experts plus a
shared expert; Granite's four multipliers.

The interface is ``decoder.py``'s (see there).  The configuration file
holds the published config.json keys; ``num_hidden_layers`` takes the
first layers of ``layer_types``, and ``num_local_experts`` is the experts
held here (ids ``expert_first`` ..), of the router's published width
``published["num_local_experts"]``.

The reference follows the published equations (HF ``GraniteMoeHybrid``):

* the embedding times ``embedding_multiplier``;
* per layer ``x + residual_multiplier * mixer(rmsnorm(x))``, then ``x +
  residual_multiplier * (moe(h) + shared(h))`` with ``h = rmsnorm(x)``;
* Mamba-2 (one group): ``in_proj`` to [z | x | B | C | dt]; a causal
  depthwise conv with bias over [x | B | C], then silu; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the sequential
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t = C_t
  h_t + D x_t``; the gated norm, gate first: ``rmsnorm(y * silu(z))``;
  ``out_proj``;
* attention: causal GQA with no rotary, scores times
  ``attention_multiplier``;
* MoE: softmax over all the router's experts, its top-k renormalised
  (GraniteMoE's softmax over the top-k logits), weighing the SwiGLU of
  each held expert; an assignment to an expert not held adds nothing;
  the shared expert ``silu(gate) * up`` from one input projection;
* logits: the tied embedding's transpose, over ``logits_scaling``.
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

# Random A_log has this spread, so that some heads decay by less than a
# factor ten over a 256-position chunk and the check sees the state
# carried from chunk to chunk (A_log near 0 forgets within a few tokens).
A_LOG_STD = 2.5


def dims(c: dict) -> dict:
    """The sizes the counters and the reference use."""
    d = c["hidden_size"]
    hq = c["num_attention_heads"]
    nh, p = c["mamba_n_heads"], c["mamba_d_head"]
    L = c["num_hidden_layers"]
    types = tuple(c["layer_types"][:L])
    return dict(
        L=L, types=types, La=types.count("attention"),
        Lm=types.count("mamba"), d=d, hq=hq,
        hkv=c["num_key_value_heads"], hd=c.get("head_dim", d // hq),
        ff=c["intermediate_size"], sff=c["shared_intermediate_size"],
        E=c.get("published", {}).get("num_local_experts",
                                     c["num_local_experts"]),
        held=c["num_local_experts"], first=c.get("expert_first", 0),
        k=c["num_experts_per_tok"], V=padded_vocab(c["vocab_size"]),
        vocab=c["vocab_size"], nh=nh, p=p, di=nh * p,
        N=c["mamba_d_state"], conv=c["mamba_d_conv"],
        chunk=c["mamba_chunk_size"], eps=float(c["rms_norm_eps"]),
        em=float(c["embedding_multiplier"]),
        am=float(c["attention_multiplier"]),
        rm=float(c["residual_multiplier"]),
        ls=float(c["logits_scaling"]))


def vocab(c: dict) -> int:
    return dims(c)["vocab"]


def check(c: dict) -> None:
    """Refuse what the program does not compute."""
    want = dict(hidden_act="silu", position_embedding_type="nope",
                mamba_n_groups=1, mamba_conv_bias=True,
                mamba_proj_bias=False, attention_bias=False,
                tie_word_embeddings=True)
    for key, value in want.items():
        if c.get(key, value) != value:
            raise SystemExit(f"config states {key}={c[key]}; the program "
                             f"computes {key}={value}")
    d = dims(c)
    if d["di"] != c["mamba_expand"] * d["d"]:
        raise SystemExit("mamba_n_heads * mamba_d_head must be "
                         "mamba_expand * hidden_size")


def arch_config(c: dict, n_layers: int | None = None):
    """The program's ``ArchConfig`` (``n_layers`` overrides the depth:
    the planner of the whole model takes the published pattern)."""
    from repro.configs.base import ArchConfig, MoeConfig, SsmConfig

    check(c)
    d = dims(c)
    n = n_layers or d["L"]
    moe = MoeConfig(n_experts=d["E"], top_k=d["k"],
                    n_held=None if d["held"] == d["E"] else d["held"],
                    first_held=d["first"], shared_d_ff=d["sff"])
    ssm = SsmConfig(state_dim=d["N"], head_dim=d["p"],
                    conv_kernel=d["conv"], expand=c["mamba_expand"],
                    chunk=d["chunk"])
    return ArchConfig(
        name=c["name"], family="interleaved", n_layers=n, d_model=d["d"],
        n_heads=d["hq"], n_kv_heads=d["hkv"], d_head=d["hd"],
        d_ff=d["ff"], vocab=d["vocab"], mlp="swiglu", tie_embeddings=True,
        rope_theta=float(c["rope_theta"]), norm_eps=d["eps"], moe=moe,
        ssm=ssm, layer_types=tuple(c["layer_types"][:n]),
        position_embedding="nope", embedding_multiplier=d["em"],
        attention_multiplier=d["am"], residual_multiplier=d["rm"],
        logits_scaling=d["ls"], source=c.get("source", ""))


def _in_proj(d: dict) -> int:
    return 2 * d["di"] + 2 * d["N"] + d["nh"]


def gemv_shapes(c: dict) -> list[tuple[int, int]]:
    """Distinct (rows, columns) of the weight GEMVs of one decode token:
    Mamba-2's projections, attention's, the router, the experts, the
    shared expert (gate and up, then down) and the head."""
    d = dims(c)
    dm = d["d"]
    shapes = [(_in_proj(d), dm), (dm, d["di"]),
              (d["hq"] * d["hd"], dm), (d["hkv"] * d["hd"], dm),
              (dm, d["hq"] * d["hd"]), (d["E"], dm), (d["ff"], dm),
              (dm, d["ff"]), (d["sff"], dm), (dm, d["sff"]), (d["V"], dm)]
    return list(dict.fromkeys(shapes))


# -- weights ----------------------------------------------------------------

def _moe_schema(d: dict) -> dict:
    dm, ff, sff, e = d["d"], d["ff"], d["sff"], d["held"]
    return {"ln1": ((dm,), None), "ln2": ((dm,), None),
            "moe/router": ((dm, d["E"]), 1 / math.sqrt(dm)),
            "moe/wi": ((e, dm, ff), 1 / math.sqrt(dm)),
            "moe/wg": ((e, dm, ff), 1 / math.sqrt(dm)),
            "moe/wo": ((e, ff, dm), 1 / math.sqrt(ff)),
            "moe/shared_wi": ((dm, 2 * sff), 1 / math.sqrt(dm)),
            "moe/shared_wo": ((sff, dm), 1 / math.sqrt(sff))}


def schema(c: dict) -> dict:
    """The program's parameter tree: the tied embedding, the final norm,
    and two stacks, ``mamba_blocks`` and ``attn_blocks``.  The program's
    config is built first, so that a program without the interleaved
    family fails here, before any weight is drawn."""
    arch_config(c)
    d = dims(c)
    dm, hq, hkv, hd = d["d"], d["hq"], d["hkv"], d["hd"]
    conv_dim = d["di"] + 2 * d["N"]
    mamba = dict(_moe_schema(d), **{
        "ssm/in_proj": ((dm, _in_proj(d)), 1 / math.sqrt(dm)),
        "ssm/conv_w": ((d["conv"], conv_dim), 1 / math.sqrt(d["conv"])),
        "ssm/conv_b": ((conv_dim,), 0.1),
        "ssm/a_log": ((d["nh"],), A_LOG_STD),
        "ssm/dt_bias": ((d["nh"],), 0.5),
        "ssm/d_skip": ((d["nh"],), 1.0),
        "ssm/norm": ((d["di"],), None),
        "ssm/out_proj": ((d["di"], dm), 1 / math.sqrt(d["di"]))})
    attn = dict(_moe_schema(d), **{
        "attn/wq": ((dm, hq * hd), 1 / math.sqrt(dm)),
        "attn/wk": ((dm, hkv * hd), 1 / math.sqrt(dm)),
        "attn/wv": ((dm, hkv * hd), 1 / math.sqrt(dm)),
        "attn/wo": ((hq * hd, dm), 1 / math.sqrt(hq * hd))})
    # the residual stream starts at std 0.02, the decoder's embedding
    # scale: an embedding of std 0.02 times embedding_multiplier would
    # make every position's top logit its own token, whatever the layers
    top = {"embed": ((d["V"], dm), 0.02 / d["em"]), "ln_f": ((dm,), None)}
    stacks = {}
    if d["Lm"]:
        stacks["mamba_blocks"] = (d["Lm"], mamba)
    if d["La"]:
        stacks["attn_blocks"] = (d["La"], attn)
    return dict(top=top, stacks=stacks)


# -- the plain reference ----------------------------------------------------

def _swiglu(h, wg, wi, wo, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wi, quant), wo,
               quant)


def moe(m, h, k: int, first: int, quant=None):
    """(routed, shared): the held experts' weighted outputs and the
    shared expert's, each (S, d)."""
    s = h.shape[0]
    probs = jax.nn.softmax(_mm(h, m["router"], quant), -1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros(probs.shape).at[jnp.arange(s)[:, None], idx].set(top)
    held = m["wi"].shape[0]
    gate = jax.lax.dynamic_slice_in_dim(gate, first, held, axis=1)

    def expert(carry, w):
        return carry, _swiglu(h, w[0], w[1], w[2], quant)

    _, ys = jax.lax.scan(expert, None, (m["wg"], m["wi"], m["wo"]))
    routed = jnp.einsum("se,esd->sd", gate, ys, precision=HI)
    g, u = jnp.split(_mm(h, m["shared_wi"], quant), 2, axis=-1)
    shared = _mm(jax.nn.silu(g) * u, m["shared_wo"], quant)
    return routed, shared


def _mamba(p, h, dm, quant):
    """Mamba-2 over the positions of ``h`` (S, d), one at a time."""
    nh, hp, n, kc, eps = dm
    s = h.shape[0]
    di = nh * hp
    proj = _mm(h, p["in_proj"], quant)
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    w = p["conv_w"].astype(jnp.float32)
    pad = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1])), xbc])
    conv = sum(pad[i:i + s] * w[i] for i in range(kc)) + p["conv_b"]
    xs, bs, cs = jnp.split(jax.nn.silu(conv), [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (S, nh)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xs = xs.reshape(s, nh, hp)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + (dt_t[:, None] * x_t)[..., None] * b_t)
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, n)), (xs, bs, cs, dt))
    y = y + xs * p["d_skip"][:, None]
    g = y.reshape(s, di) * jax.nn.silu(z)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    g = g * (1.0 + p["norm"].astype(jnp.float32))
    return _mm(g, p["out_proj"], quant)


BLOCK_Q = 512


def _attention(a, h, da, quant):
    """Causal GQA without rotary, in blocks of ``BLOCK_Q`` queries."""
    hq, hkv, hd, am = da
    s = h.shape[0]
    q = _mm(h, a["wq"], quant).reshape(s, hq, hd)
    kk = jnp.repeat(_mm(h, a["wk"], quant).reshape(s, hkv, hd),
                    hq // hkv, axis=1)        # query head i -> kv i // rep
    v = jnp.repeat(_mm(h, a["wv"], quant).reshape(s, hkv, hd), hq // hkv,
                   axis=1)
    bq = min(BLOCK_Q, s)
    nb = -(-s // bq)
    qb = jnp.pad(q, ((0, nb * bq - s), (0, 0), (0, 0))).reshape(
        nb, bq, hq, hd)

    def block(inp):
        i, qi = inp
        sc = jnp.einsum("qhd,khd->hqk", qi, kk, precision=HI) * am
        rows = i * bq + jnp.arange(bq)
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)

    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(nb * bq, hq, hd)
    return _mm(o[:s].reshape(s, hq * hd), a["wo"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "dm", "quant"))
def _layer(p, x, kind, dm, quant):
    eps, rm, k, first, mix = dm
    h = _norm(x, p["ln1"], eps)
    if kind == "attention":
        y = _attention(p["attn"], h, mix, quant)
    else:
        y = _mamba(p["ssm"], h, mix, quant)
    x = x + rm * y
    routed, shared = moe(p["moe"], _norm(x, p["ln2"], eps), k, first,
                         quant)
    return x + rm * (routed + shared)


@functools.partial(jax.jit, static_argnames=("eps", "ls", "quant"))
def _head(x, ln_f, head, eps, ls, quant):
    return _mm(_norm(x, ln_f, eps), head, quant) / ls


def logits(params, c: dict, tokens: np.ndarray, length: int,
           quant: str | None = None) -> jnp.ndarray:
    """Logits ``(len(tokens), V)`` of one sequence, padded to ``length``
    positions for the compile (causal: padding comes after), one layer
    at a time in ``layer_types`` order."""
    d = dims(c)
    n = len(tokens)
    ids = np.zeros(length, np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32) * d["em"]
    mixers = {"mamba": (d["nh"], d["p"], d["N"], d["conv"], d["eps"]),
              "attention": (d["hq"], d["hkv"], d["hd"], d["am"])}
    stacks = {"mamba": "mamba_blocks", "attention": "attn_blocks"}
    seen = {"mamba": 0, "attention": 0}
    for kind in d["types"]:
        i = seen[kind]
        seen[kind] += 1
        p = jax.tree.map(lambda a: a[i], params[stacks[kind]])
        x = _layer(p, x, kind, (d["eps"], d["rm"], d["k"], d["first"],
                                mixers[kind]), quant)
    return _head(x, params["ln_f"], params["embed"].T, d["eps"], d["ls"],
                 quant)[:n]


# -- counts (bench/counts.py says how they are counted) ---------------------

def _mamba_params(d: dict) -> int:
    conv_dim = d["di"] + 2 * d["N"]
    return (d["d"] * _in_proj(d) + d["di"] * d["d"]
            + (d["conv"] + 1) * conv_dim + 3 * d["nh"] + d["di"])


def _attn_params(d: dict) -> int:
    return d["d"] * d["hd"] * (2 * d["hq"] + 2 * d["hkv"])


def _moe_fixed(d: dict) -> int:
    """Router, shared expert and the layer's two norms."""
    return d["d"] * d["E"] + 3 * d["d"] * d["sff"] + 2 * d["d"]


def experts_reached(d: dict, batch: int) -> float:
    """Expected held experts that ``batch`` tokens reach with ``k`` of the
    router's ``E`` each, under uniform routing."""
    return d["held"] * (1.0 - (1.0 - d["k"] / d["E"]) ** batch)


def decode_weight_bytes(c: dict, batch: int) -> float:
    d = dims(c)
    per = d["L"] * (_moe_fixed(d) + experts_reached(d, batch) * 3 * d["d"]
                    * d["ff"])
    mix = d["Lm"] * _mamba_params(d) + d["La"] * _attn_params(d)
    head = d["d"] * d["V"]
    gathered = batch * d["d"]
    return BYTES * (per + mix + head + d["d"] + gathered)


def state_bytes(c: dict, context: int) -> float:
    """One slot's state at ``context`` live positions: keys and values of
    the attention layers, read; SSM and conv state of the Mamba layers,
    read and written."""
    d = dims(c)
    kv = 2 * d["La"] * d["hkv"] * d["hd"] * context
    rec = d["Lm"] * (d["nh"] * d["p"] * d["N"]
                     + (d["conv"] - 1) * (d["di"] + 2 * d["N"]))
    return BYTES * (kv + 2 * rec)


def token_flops(c: dict, context: int, logits: bool) -> float:
    """Model FLOPs of one token at position ``context - 1``: every weight
    matrix it passes (of the experts, top-k times the held share), the
    SSM update (per state element a decay multiply-add, the input's outer
    product and the read-out: 3 multiply-adds) and the conv, causal
    attention at the live context."""
    d = dims(c)
    experts = d["k"] * d["held"] / d["E"] * 3 * d["d"] * d["ff"]
    lin = (d["L"] * (d["d"] * d["E"] + 3 * d["d"] * d["sff"] + experts)
           + d["Lm"] * (d["d"] * _in_proj(d) + d["di"] * d["d"])
           + d["La"] * _attn_params(d))
    if logits:
        lin += d["d"] * d["V"]
    ssm = d["Lm"] * (3 * d["nh"] * d["p"] * d["N"]
                     + d["conv"] * (d["di"] + 2 * d["N"]))
    attn = d["La"] * 2 * context * d["hq"] * d["hd"]
    return 2.0 * (lin + ssm + attn)


def param_bytes(c: dict) -> float:
    d = dims(c)
    per = d["L"] * (_moe_fixed(d) + d["held"] * 3 * d["d"] * d["ff"])
    mix = d["Lm"] * _mamba_params(d) + d["La"] * _attn_params(d)
    return BYTES * (per + mix + d["V"] * d["d"] + d["d"])
