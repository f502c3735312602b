"""The serving cache is updated in place.

``jit_decode_step`` and ``jit_prefill`` donate their cache, and the layer
scan carries the whole cache and writes only each layer's new entries, so
the compiled step aliases the cache it is given and holds no second copy.
Donation changes where results are written, never what they are: the
donated jits must agree bit for bit with undonated ones.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine

# dense, local/global, MoE, SSM-hybrid
KINDS = ["granite-8b", "gemma3-4b", "granite-moe-3b-a800m", "hymba-1.5b"]


@pytest.fixture(scope="module", params=KINDS)
def lm(request):
    cfg = smoke_config(ARCHS[request.param])
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _nbytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def _undonated(cfg):
    return (jax.jit(lambda p, b, c: M.prefill(cfg, p, b, c)),
            jax.jit(lambda p, c, t, q: M.decode_step(cfg, p, c, t, q)))


@pytest.mark.parametrize("which", ["decode_step", "prefill"])
def test_compiled_step_aliases_its_cache(lm, which):
    """Every byte of the cache is aliased from input to output, and the
    step's scratch holds less than one cache: no whole-cache copy."""
    cfg, params = lm
    # 4096 positions: one cache then outweighs the step's other scratch
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
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= _nbytes(cache)
    assert ma.temp_size_in_bytes < _nbytes(cache)


def _admit(prefill, cfg, params, cache, slot, prompt):
    one = M.init_cache(cfg, 1, 32, jnp.float32)
    logits, one = prefill(params, {"tokens": prompt[None]}, one)
    cache = jax.tree.map(lambda f, o: f.at[:, slot:slot + 1].set(o),
                         cache, one)
    return logits, cache


def test_donated_steps_match_undonated(lm):
    """Ragged decode steps with an admission mid-stream: the donated jits
    give the same logits and caches, bit for bit, as undonated jits fed
    copies, and the caches they were given are consumed."""
    cfg, params = lm
    rng = np.random.default_rng(3)
    ref_prefill, ref_decode = _undonated(cfg)
    prefill, decode = M.jit_prefill(cfg), M.jit_decode_step(cfg)
    slots = 3
    got = M.init_cache(cfg, slots, 32, jnp.float32)
    ref = M.init_cache(cfg, slots, 32, jnp.float32)
    pos = np.zeros(slots, np.int32)

    def admit(slot, n):
        nonlocal got, ref
        prompt = jnp.asarray(rng.integers(0, cfg.vocab, n), jnp.int32)
        lg, got = _admit(prefill, cfg, params, got, slot, prompt)
        lr, ref = _admit(ref_prefill, cfg, params, ref, slot, prompt)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lr))
        pos[slot] = n

    admit(0, 5)
    admit(1, 9)
    for step in range(6):
        if step == 3:
            admit(2, 4)
        tok = jnp.asarray(rng.integers(0, cfg.vocab, (slots, 1)), jnp.int32)
        q = jnp.asarray(pos)
        ref_in = jax.tree.map(jnp.copy, ref)
        lr, ref = ref_decode(params, ref_in, tok, q)
        given = got
        lg, got = decode(params, got, tok, q)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(given))
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(ref_in))
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lr))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), got, ref)
        pos[pos > 0] += 1


def _streams(cfg, params, undonated: bool):
    eng = ServingEngine(cfg, params, slots=2, max_seq=48)
    if undonated:
        eng._prefill_fn, eng._decode = _undonated(cfg)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=4 + 3 * i),
                    max_new=3 + 2 * i) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=100)
    assert all(r.done for r in reqs)
    return [list(r.out) for r in reqs]


def test_engine_streams_unchanged_by_donation(lm):
    """``ServingEngine``'s greedy token streams, slots refilled mid-run,
    are those of the same engine on undonated steps."""
    cfg, params = lm
    assert _streams(cfg, params, False) == _streams(cfg, params, True)
