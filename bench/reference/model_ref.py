"""Plain float32 reference of the served decoder, and its fp8 control.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, no cache and no
batching; it imports nothing of the program.  It follows the published
Llama/Granite block: RMSNorm (weight ``1 + gamma``, the program's
parameterisation of a norm weight), rotary embedding on the two halves
of each head, grouped-query causal attention, SwiGLU, or a router whose
softmax's top-k, renormalised, weighs the experts' SwiGLU outputs.  The
head is the embedding's transpose where the configuration ties them.

It runs one layer at a time over one padded sequence, so that it fits
beside the parameters: one compile per layer shape and length.

``quant="fp8"`` is the control: every weight and every activation that
enters a weight matrix is rounded to float8 e4m3 with one scale per
tensor, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q(x, quant):
    if quant is None:
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    return jnp.matmul(_q(x, quant), _q(w.astype(jnp.float32), quant),
                      precision=HI)


def _norm(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))


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


def logits(params, d: dict, tokens: np.ndarray, length: int,
           quant: str | None = None) -> jnp.ndarray:
    """Logits ``(len(tokens), V)`` of one sequence, padded to ``length``
    positions for the compile (causal attention: padding comes after)."""
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


def served_gaps(ref: np.ndarray, prompt_len: int,
                served: list[int]) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at the position that produced it."""
    pos = prompt_len - 1 + np.arange(len(served))
    rows = ref[pos]
    return rows.max(-1) - rows[np.arange(len(served)), served]


def control_gaps(ref: np.ndarray, ctl: np.ndarray, prompt_len: int,
                 n: int) -> np.ndarray:
    """Per position, the gap of the token the control puts first."""
    pos = prompt_len - 1 + np.arange(n)
    top = ctl[pos].argmax(-1)
    rows = ref[pos]
    return rows.max(-1) - rows[np.arange(n), top]
