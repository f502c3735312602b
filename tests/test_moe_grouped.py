"""The MoE block's grouped dispatch (``models/moe.py``): assignments
sorted by expert, grouped matmuls over the routed rows only.  It gives
the buffer's results, and runs where the shape says: prefill takes it,
decode keeps the buffer.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model as M
from repro.models import moe as MOE

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _moe(e_all, held, first, shared, key=0, d=32, ff=24):
    p = MOE.moe_init(jax.random.PRNGKey(key), d, ff, e_all, "swiglu")
    if held != e_all:
        p = dict(p, **{w: p[w][first:first + held]
                       for w in ("wi", "wg", "wo")})
    if shared:
        p.update(MOE.shared_init(jax.random.PRNGKey(key + 1), d, 40))
    return p


def _apply(p, x, k, first, grouped=None):
    """``moe_apply``, its dispatch forced to one path (or the rule's)."""
    with pytest.MonkeyPatch.context() as mp:
        if grouped is not None:
            mp.setattr(MOE, "_grouped", lambda *_: grouped)
        return MOE.moe_apply(p, x, top_k=k, capacity_factor=1.0,
                             mlp_kind="swiglu", first=first)


def _threshold(k, e_all):
    """The fewest tokens the rule sends down the grouped path."""
    return MOE.OVERHEAD_ROWS * e_all // (e_all - k) + 1


# (n, e_all, held, first, k, shared, skew): the whole layer; a share with
# a shared expert; more slots than held experts (granite-4.0-h's 10 of
# 9); a router that sends every token to held experts, so the sorted
# rows fill their bound; n on either side of the rule's threshold
CASES = {
    "whole": (50, 8, 8, 0, 2, False, False),
    "share": (50, 16, 2, 6, 4, True, False),
    "k_over_held": (40, 72, 9, 18, 10, True, False),
    "skewed": (30, 16, 4, 8, 4, True, True),
    "below": (_threshold(2, 8) - 1, 8, 8, 0, 2, False, False),
    "above": (_threshold(2, 8), 8, 8, 0, 2, False, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_path_equals_the_buffer(case):
    n, e_all, held, first, k, shared, skew = CASES[case]
    p = _moe(e_all, held, first, shared)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, n, 32))
    if skew:
        x = x.at[..., 0].set(1.0)
        p["router"] = p["router"].at[0, first:first + held].add(50.0)
        top = np.asarray(jax.lax.top_k(
            x.reshape(n, -1) @ p["router"], k)[1]) - first
        assert ((top >= 0) & (top < held)).all()
    want, aux_want = _apply(p, x, k, first, False)
    if case in ("below", "above"):
        assert MOE._grouped(n, k, e_all) == (case == "above")
        got, aux = _apply(p, x, k, first)
    else:
        got, aux = _apply(p, x, k, first, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) == float(aux_want)


def test_grouped_path_gradients_equal_the_buffers():
    """A training step through either path takes the same gradient."""
    p = _moe(8, 8, 0, False)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 25, 32))
    r = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def grads(grouped):
        def loss(p, x):
            return (_apply(p, x, 2, 0, grouped)[0] * r).sum()
        return jax.grad(loss, argnums=(0, 1))(p, x)

    for g, w in zip(jax.tree.leaves(grads(True)),
                    jax.tree.leaves(grads(False))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_expert_shares_sum_to_the_uncut_layer_grouped():
    """Eight chips' shares of 16 experts (2 each, top-4), each through the
    grouped path: with the shared expert counted once, they add up to the
    whole layer through the buffer."""
    d, e, k = 32, 16, 4
    p = _moe(e, e, 0, True, key=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, d))
    whole, _ = _apply(p, x, k, 0, False)
    shared = MOE.shared_apply(p, x.reshape(-1, d)).reshape(x.shape)
    parts = []
    for first in range(0, e, 2):
        share = dict(p, **{w: p[w][first:first + 2]
                           for w in ("wi", "wg", "wo")})
        y, _ = _apply(share, x, k, first, True)
        parts.append(y - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def cells():
    """The program's configs of the two MoE cells' configurations, with a
    prompt of each cell that the rule sends grouped (granitemoe-batch
    deals 384-1536 tokens by 128 and keeps the buffer at 384; 640, since
    512 is also its experts' width; granite4h-longdoc's shortest of
    2048-12288), slots and positions."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    import registry
    out = {}
    for name, prompt in (("granite-moe-3b-a800m", 640),
                         ("granite-4.0-h-small", 2048)):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            c = json.load(f)
        out[name] = (registry.arch(c).arch_config(c), prompt,
                     c["serve"]["slots"], c["serve"]["max_seq"])
    return out


@pytest.mark.parametrize("name", ["granite-moe-3b-a800m",
                                  "granite-4.0-h-small"])
def test_prefill_takes_the_grouped_path(cells, name):
    """The cell's grouped prefill runs its experts as grouped
    matmuls under the ``moe.grouped`` scope, and builds no ``(e, n, d)``
    buffer of the held experts over every token; in the whole layer the
    sorted rows (``n·k``) are fewer than the buffer's ``e·n``."""
    cfg, n, _, max_seq = cells[name]
    params = jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 1, max_seq, jnp.float32))
    traced = M.jit_prefill(cfg).trace(
        params, {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32)}, cache)
    assert "moe.routed/moe.grouped/" in traced.lower().as_text(
        debug_info=True)
    jaxpr = str(traced.jaxpr)
    e, k, d = cfg.moe.n_held or cfg.moe.n_experts, cfg.moe.top_k, cfg.d_model
    assert "ragged_dot_general" in jaxpr
    assert f"bf16[{e},{n},{d}]" not in jaxpr
    assert (f"bf16[{e * n},{d}]" in jaxpr) == (k >= e)


@pytest.mark.parametrize("name", ["granite-moe-3b-a800m",
                                  "granite-4.0-h-small"])
def test_decode_step_keeps_the_buffer(cells, name, monkeypatch):
    """The cell's decode step lowers to the program the buffer alone
    gives: the rule leaves every slot's single token to it."""
    cfg, _, slots, max_seq = cells[name]
    params = jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, slots, max_seq, jnp.float32))

    def lowered():
        return M.jit_decode_step(cfg).lower(
            params, cache, jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))

    text = lowered().as_text(debug_info=True)
    assert "moe.routed/" in text and "moe.grouped" not in text
    text = lowered().as_text()
    monkeypatch.setattr(MOE, "_grouped", lambda *_: False)
    assert lowered().as_text() == text
