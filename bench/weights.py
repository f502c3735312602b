"""Seeded model weights, made on the device in one jitted call.

The tree has the program's parameter names (``models/model.py``), which
are the interface of the system under test; the values and their scales
are the benchmark's own, so the reference can use the same weights
without taking anything the program made.  Stacked layer leaves are made
one layer at a time inside the call (``lax.map``), so the transient
random bits never exceed one layer's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import registry

NORM_STD = 0.1


def padded_vocab(vocab: int) -> int:
    """The program pads its embedding and head to a multiple of 256."""
    return -(-vocab // 256) * 256


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
    """The whole parameter tree for config file ``c`` from ``seed``, drawn
    from its architecture's ``schema``: ``{"top": {path: (shape, std)},
    "stacks": {name: (count, {path: (shape, std)})}}``; std None is a
    norm weight, stored as the program's ``gamma`` (scale ``1 + gamma``).
    Each stack gets a key of its own, and each of its layers a key split
    from that one."""
    s = registry.arch(c).schema(c)
    top, stacks = s["top"], s["stacks"]

    @jax.jit
    def build(key):
        k_top, *k_stacks = jax.random.split(key, 1 + len(stacks))
        flat = {p: _draw(jax.random.fold_in(k_top, i), sh, sd, dtype)
                for i, (p, (sh, sd)) in enumerate(top.items())}
        out = _nest(flat)
        for k, (name, (n, lay)) in zip(k_stacks, stacks.items()):
            def one_layer(k, lay=lay):
                return {p: _draw(jax.random.fold_in(k, i), sh, sd, dtype)
                        for i, (p, (sh, sd)) in enumerate(lay.items())}

            out[name] = _nest(jax.lax.map(one_layer,
                                          jax.random.split(k, n)))
        return out

    return build(jax.random.PRNGKey(seed))
