"""The interleaved family (GraniteMoeHybrid: Mamba-2 and NoPE attention
layers, an expert share plus a shared expert, Granite's multipliers)
against its plain reference, ``bench/arch/granitehybrid.py``, at a CPU
size with seeded random weights; and the uniform families' programs
unchanged by what it added.
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.configs.base import MoeConfig
from repro.models import model as M
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import decode_gemv_sites

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules: the reference, the weights, the tiny
    hybrid configuration."""
    for p in (BENCH, os.path.join(BENCH, "tests")):
        if p not in sys.path:
            sys.path.append(p)
    import registry
    import weights
    from test_granitehybrid import TINY_HYBRID as c
    return registry.arch(c), weights, c


@pytest.fixture(scope="module")
def hybrid(bench):
    arch, weights, c = bench
    cfg = arch.arch_config(c)
    return arch, c, cfg, weights.make_params(c, 7, dtype=jnp.float32)


def test_prefill_then_decode_match_the_reference(hybrid):
    """A prompt of several 16-position chunks, prefilled into the cache,
    then decoded token by token through it: the logits at every position
    are the reference's, whose Mamba-2 runs its recurrence one position
    at a time.  Some heads keep over a tenth of their state across a
    whole chunk, so the state carried between chunks is compared."""
    arch, c, cfg, params = hybrid
    a_log = np.asarray(params["mamba_blocks"]["ssm"]["a_log"])
    assert np.exp(-np.exp(a_log) * cfg.ssm.chunk).max() > 0.1
    n, extra = 70, 8
    toks = np.random.default_rng(0).integers(0, c["vocab_size"],
                                             n + extra).astype(np.int32)
    ref = np.asarray(arch.logits(params, c, toks, 96))
    cache = M.init_cache(cfg, 1, 96, jnp.float32)
    lg, cache = M.jit_prefill(cfg)(
        params, {"tokens": jnp.asarray(toks[:n])[None]}, cache)
    got = [np.asarray(lg[0])]
    decode = M.jit_decode_step(cfg)
    for j in range(extra):
        tok = jnp.asarray(toks[n + j:n + j + 1])[None]
        lg, cache = decode(params, cache, tok,
                           jnp.asarray([n + j], jnp.int32))
        got.append(np.asarray(lg[0]))
    want = ref[n - 1:n + extra]
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.stack(got)[:, :want.shape[1]], want,
                               rtol=0, atol=1e-5 * scale)
    full, _ = M.forward(cfg, params, {"tokens": jnp.asarray(toks)[None]},
                        remat=False)
    np.testing.assert_allclose(np.asarray(full[0])[:, :ref.shape[1]], ref,
                               rtol=0, atol=1e-5 * scale)


def test_engine_serves_the_reference_tokens(hybrid):
    """``ServingEngine`` (donated cache, slots refilled mid-run): every
    served token is the reference's best at its position, within float32
    rounding."""
    arch, c, cfg, params = hybrid
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, c["vocab_size"],
                                               20 + 9 * i).astype(np.int32),
                    max_new=4 + 2 * i) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=100)
    assert all(r.done for r in reqs)
    for r in reqs:
        toks = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        ref = np.asarray(arch.logits(params, c, toks, 64))
        rows = ref[len(r.prompt) - 1 + np.arange(len(r.out))]
        gap = rows.max(-1) - rows[np.arange(len(r.out)), r.out]
        assert gap.max() <= 1e-5 * np.abs(rows).max(), gap


def test_cache_holds_each_kind_only_where_it_lives(hybrid):
    _, _, cfg, _ = hybrid
    cache = M.init_cache(cfg, 3, 40, jnp.float32)
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    na, nm = 1, 3
    assert set(cache) == {"kv", "ssm", "conv"}
    for kv in cache["kv"]:
        assert kv.shape == (na, 3, cfg.n_kv_heads, 40, cfg.d_head)
    assert cache["ssm"].shape == (nm, 3, cfg.n_ssm_heads, cfg.ssm.head_dim,
                                  cfg.ssm.state_dim)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (nm, 3, cfg.ssm.conv_kernel - 1,
                                   cfg.d_inner + 2 * cfg.ssm.state_dim)
    assert M.segments(cfg) == [("mamba", 0, 2), ("attention", 0, 1),
                               ("mamba", 2, 3)]


@pytest.mark.parametrize("which", ["decode_step", "prefill"])
def test_hybrid_step_aliases_its_cache(hybrid, which):
    """Both kinds of state are a donated carry: every byte of the cache
    is aliased from input to output.  (That the chip's compiler keeps no
    copy of it is ``test_tpu_compile.py``'s: the CPU compiler copies the
    K/V of a lone attention layer, whose slice it reads as a bitcast.)"""
    _, _, cfg, params = hybrid
    nbytes = lambda t: sum(x.nbytes for x in jax.tree.leaves(t))  # noqa
    if which == "decode_step":
        cache = M.init_cache(cfg, 4, 4096, jnp.float32)
        compiled = M.jit_decode_step(cfg).lower(
            params, cache, jnp.zeros((4, 1), jnp.int32),
            jnp.zeros((4,), jnp.int32)).compile()
    else:
        cache = M.init_cache(cfg, 1, 4096, jnp.float32)
        compiled = M.jit_prefill(cfg).lower(
            params, {"tokens": jnp.zeros((1, 7), jnp.int32)},
            cache).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes(cache)


def _moe_params(key, d, ff, e, sff):
    p = MOE.moe_init(key, d, ff, e, "swiglu")
    p.update(MOE.shared_init(jax.random.fold_in(key, 1), d, sff))
    return p


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of 16 experts (2 each, top-4 routing over all
    16): their outputs, with the shared expert counted once, add up to
    the whole layer's; and the whole layer is the plain per-token sum."""
    d, ff, e, sff, k = 32, 24, 16, 40, 4
    p = _moe_params(jax.random.PRNGKey(3), d, ff, e, sff)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, d))
    kw = dict(top_k=k, capacity_factor=1.0, mlp_kind="swiglu")
    whole, _ = MOE.moe_apply(p, x, **kw)
    shared = MOE.shared_apply(p, x.reshape(-1, d)).reshape(x.shape)
    parts = []
    for first in range(0, e, 2):
        share = dict(p, **{w: p[w][first:first + 2]
                           for w in ("wi", "wg", "wo")})
        y, _ = MOE.moe_apply(share, x, first=first, **kw)
        parts.append(y - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    # the plain sum over each token's top-k experts
    xt = np.asarray(x.reshape(-1, d), np.float64)
    probs = jax.nn.softmax(jnp.asarray(xt @ np.asarray(p["router"])), -1)
    want = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-np.asarray(probs[t]))[:k]
        g = np.asarray(probs[t])[top] / np.asarray(probs[t])[top].sum()
        for gi, j in zip(g, top):
            h = xt[t] @ np.asarray(p["wg"][j])
            h = h / (1 + np.exp(-h)) * (xt[t] @ np.asarray(p["wi"][j]))
            want[t] += gi * (h @ np.asarray(p["wo"][j]))
    want += np.asarray(shared.reshape(-1, d))
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, d), want,
                               rtol=1e-4, atol=1e-4)


def test_gated_norm_is_gate_then_norm():
    """Mamba-2's gated RMSNorm multiplies by ``silu(z)`` before the norm,
    with the configuration's eps; norm-then-gate is another function."""
    rng = np.random.default_rng(1)
    y, z = rng.normal(size=(2, 3, 48)), 3 * rng.normal(size=(2, 3, 48))
    gamma = 0.1 * rng.normal(size=48)
    eps = 1e-5
    got = np.asarray(SSM.gated_norm(jnp.asarray(y), jnp.asarray(z),
                                    jnp.asarray(gamma), eps))
    g = y * z / (1 + np.exp(-z))
    want = g / np.sqrt((g * g).mean(-1, keepdims=True) + eps) * (1 + gamma)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    other = y / np.sqrt((y * y).mean(-1, keepdims=True) + eps) \
        * (1 + gamma) * z / (1 + np.exp(-z))
    assert np.abs(got - other).max() > 0.1


# SHA-256 of the lowered programs of the uniform families' smoke configs
# (2 slots x 64 positions, a 9-token prompt) as they were lowered before
# the multipliers, the expert share and the interleaved family were
# added: a multiplier of 1 and a whole expert set add no operation.
LOWERED = {
    ("granite-8b", "prefill"):
        "8eeedcb455e3a517cbf410614ad9cfbd554c1c9612c8ee5ea44f8dd820980954",
    ("granite-8b", "decode_step"):
        "36b7d9f1fd150b8f0501c8e692374f3ca5132e0953006c35e8760ff8b722bfed",
    ("granite-moe-3b-a800m", "prefill"):
        "b69d6eff60ee2f39fb4bce6de8240898726bde727ac8d8df4baeff195d324f35",
    ("granite-moe-3b-a800m", "decode_step"):
        "a5cf8255ba5f83ce3b9fb014d5e32fd2a9b58fa42ecbbf0908e9c40ea17a6b08",
}


def _lowered(cfg, which: str) -> str:
    params = jax.eval_shape(lambda: M.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    i32 = jnp.int32
    if which == "prefill":
        one = jax.eval_shape(lambda: M.init_cache(cfg, 1, 64, jnp.float32))
        return M.jit_prefill(cfg).lower(
            params, {"tokens": jax.ShapeDtypeStruct((1, 9), i32)},
            one).as_text()
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 64, jnp.float32))
    return M.jit_decode_step(cfg).lower(
        params, cache, jax.ShapeDtypeStruct((2, 1), i32),
        jax.ShapeDtypeStruct((2,), i32)).as_text()


@pytest.mark.parametrize("name,which", sorted(LOWERED))
def test_unit_multipliers_add_no_operation(name, which):
    cfg = smoke_config(ARCHS[name])
    text = _lowered(cfg, which)
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[
        (name, which)]
    import dataclasses
    ones = dataclasses.replace(cfg, embedding_multiplier=1,
                               residual_multiplier=1.0, logits_scaling=1)
    assert _lowered(ones, which) == text
    scaled = dataclasses.replace(cfg, residual_multiplier=0.22)
    assert _lowered(scaled, which) != text


def test_sites_count_each_kind_over_its_layers(bench):
    """The 40-layer planner of granite-4.0-h-small: attention sites in
    its 4 attention layers, SSM sites in its 36 Mamba layers, the router
    and the shared expert in all 40, the routed experts top-10 of 72."""
    arch, _, _ = bench
    import registry
    c = registry.config(registry.load_benchmark(), "granite-4.0-h-small")
    cfg = arch.arch_config(c, n_layers=40)
    sites = {s.name: (s.h, s.w, s.count) for s in decode_gemv_sites(cfg)}
    assert sites == {
        "attn.wq": (4096, 4096, 4), "attn.wk": (1024, 4096, 4),
        "attn.wv": (1024, 4096, 4), "attn.wo": (4096, 4096, 4),
        "moe.router": (72, 4096, 40), "moe.w0": (768, 4096, 400),
        "moe.w1": (768, 4096, 400), "moe.wo": (4096, 768, 400),
        "moe.shared_wg": (1536, 4096, 40), "moe.shared_wi": (1536, 4096, 40),
        "moe.shared_wo": (4096, 1536, 40),
        "ssm.in_proj": (16768, 4096, 36), "ssm.out_proj": (4096, 8192, 36),
        "lm_head": (100352, 4096, 1)}
    assert arch.arch_config(c).layer_types == (
        "mamba",) * 5 + ("attention",) + ("mamba",) * 4


def test_shared_expert_and_share_in_a_uniform_moe():
    """The uniform MoE family takes the share and the shared expert too:
    its layer holds ``n_held`` experts and a shared expert, and its
    forward runs."""
    import dataclasses
    base = smoke_config(ARCHS["granite-moe-3b-a800m"])
    cfg = dataclasses.replace(base, moe=MoeConfig(
        n_experts=4, top_k=2, n_held=2, first_held=1, shared_d_ff=16))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    moe = params["blocks"]["moe"]
    assert moe["router"].shape[-1] == 4 and moe["wi"].shape[1] == 2
    assert moe["shared_wi"].shape[-1] == 32
    logits, _ = M.forward(cfg, params, {"tokens": jnp.ones((1, 5),
                                                           jnp.int32)})
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("which", ["decode_step", "prefill"])
def test_device_ops_carry_the_mechanisms_names(hybrid, which):
    """The compiled step's operations are named by ``jax.named_scope``:
    the Mamba-2 mixer, its state update (the recurrence in decode, the
    chunked scan in prefill), attention, the routed and shared experts."""
    _, _, cfg, params = hybrid
    if which == "decode_step":
        text = M.jit_decode_step(cfg).lower(
            params, M.init_cache(cfg, 2, 32, jnp.float32),
            jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32)).compile().as_text()
    else:
        text = M.jit_prefill(cfg).lower(
            params, {"tokens": jnp.zeros((1, 20), jnp.int32)},
            M.init_cache(cfg, 1, 32, jnp.float32)).compile().as_text()
    for scope in ("mamba.mix/", "mamba.mix/mamba.state/", "/attn/",
                  "moe.routed/", "moe.shared/"):
        assert scope in text, scope


def test_merge_span_counts_the_slots_state(hybrid):
    """``serve.merge`` carries the bytes of the slot it merges: K/V of the
    attention layer, SSM and conv state of the Mamba layers."""
    from repro import obs
    _, c, cfg, params = hybrid
    eng = ServingEngine(cfg, params, slots=2, max_seq=32)
    obs.enable()
    try:
        eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                           max_new=2))
        eng.run(max_steps=10)
    finally:
        obs.disable()
    (merge,) = [r for r in obs.take() if r.name == "serve.merge"]
    one = M.init_cache(cfg, 1, 32, jnp.float32)
    assert merge.attrs == dict(
        kv_bytes=sum(x.nbytes for x in one["kv"]),
        state_bytes=one["ssm"].nbytes + one["conv"].nbytes)
