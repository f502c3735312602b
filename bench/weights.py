"""Seeded model weights, made on the device in one jitted call.

The tree has the program's parameter names (``models/model.py``), which
are the interface of the system under test; the values and their scales
are the benchmark's own, so the reference can use the same weights
without taking anything the program made.  Stacked layer leaves are made
one layer at a time inside the call (``lax.map``), so the transient
random bits never exceed one layer's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from model_config import dims

NORM_STD = 0.1


def layer_schema(c: dict) -> dict:
    """{path: (shape, std)} of one decoder layer; std None = a norm
    weight, stored as the program's ``gamma`` (scale ``1 + gamma``)."""
    d = dims(c)
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


def top_schema(c: dict) -> dict:
    d = dims(c)
    s = {"embed": ((d["V"], d["d"]), 0.02), "ln_f": ((d["d"],), None)}
    if not d["tied"]:
        s["lm_head"] = ((d["d"], d["V"]), 0.02)
    return s


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def _draw(key, shape, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = x * (NORM_STD if std is None else std)
    return x.astype(dtype)


def make_params(c: dict, seed: int, dtype=jnp.bfloat16):
    """The whole parameter tree for config file ``c`` from ``seed``."""
    L = dims(c)["L"]
    lay, top = layer_schema(c), top_schema(c)

    @jax.jit
    def build(key):
        k_top, k_lay = jax.random.split(key)
        flat = {p: _draw(jax.random.fold_in(k_top, i), sh, sd, dtype)
                for i, (p, (sh, sd)) in enumerate(top.items())}

        def one_layer(k):
            return {p: _draw(jax.random.fold_in(k, i), sh, sd, dtype)
                    for i, (p, (sh, sd)) in enumerate(lay.items())}

        blocks = jax.lax.map(one_layer, jax.random.split(k_lay, L))
        out = _nest(flat)
        out["blocks"] = _nest(blocks)
        return out

    return build(jax.random.PRNGKey(seed))
