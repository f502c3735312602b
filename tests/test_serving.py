"""Serving engine + PIM offload planner tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.core.pimsim import PimSimulator
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine
from repro.serving.offload import OffloadPlanner, decode_gemv_sites


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config(ARCHS["granite-8b"])
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_continuous_batching_completes(small_lm):
    cfg, params = small_lm
    eng = ServingEngine(cfg, params, slots=3, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=5 + i),
                    max_new=4 + i % 3) for i in range(7)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run(max_steps=200)
    assert all(r.done for r in reqs)
    assert all(len(r.out) >= 4 for r in reqs)
    assert stats["prefills"] == 7
    assert stats["tokens"] > 0


def test_batched_decode_matches_single(small_lm):
    """Ragged batched decode == one-by-one decode (slot isolation)."""
    cfg, params = small_lm
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, cfg.vocab, size=6)
    p2 = rng.integers(0, cfg.vocab, size=9)

    def greedy(prompt, n=3):
        cache = M.init_cache(cfg, 1, 64, jnp.float32)
        logits, cache = M.prefill(cfg, params,
                                  {"tokens": jnp.asarray(prompt)[None]},
                                  cache)
        toks = [int(jnp.argmax(logits[0]))]
        pos = len(prompt)
        for _ in range(n - 1):
            logits, cache = M.decode_step(
                cfg, params, cache,
                jnp.asarray([[toks[-1]]], jnp.int32),
                jnp.asarray(pos, jnp.int32))
            toks.append(int(jnp.argmax(logits[0])))
            pos += 1
        return toks

    want1, want2 = greedy(p1), greedy(p2)
    eng = ServingEngine(cfg, params, slots=2, max_seq=64)
    r1 = Request(rid=1, prompt=p1, max_new=3)
    r2 = Request(rid=2, prompt=p2, max_new=3)
    eng.submit(r1)
    eng.submit(r2)
    eng.run(max_steps=50)
    assert r1.out == want1, (r1.out, want1)
    assert r2.out == want2, (r2.out, want2)


def test_zero_request_summary_is_neutral(small_lm):
    """A run that completes nothing (no submissions, or a step budget of
    zero) summarizes to neutral values — no raise, no 0/0: completed 0,
    in_flight counts the queue, tokens_per_step 0.0."""
    cfg, params = small_lm
    eng = ServingEngine(cfg, params, slots=2, max_seq=32)
    assert eng.step() is False         # idle tick: admits nothing, no act
    out = eng.run(max_steps=3)
    assert out["steps"] == 0 and out["tokens"] == 0
    assert out["prefills"] == 0 and out["completed"] == 0
    assert out["in_flight"] == 0 and out["tokens_per_step"] == 0.0
    assert out["batch_occupancy"] == {}
    # queued-but-never-stepped requests count as in flight
    eng2 = ServingEngine(cfg, params, slots=2, max_seq=32)
    eng2.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2))
    out2 = eng2.summary()
    assert out2["in_flight"] == 1 and out2["completed"] == 0
    assert out2["tokens_per_step"] == 0.0


def test_engine_records_admit_and_completion_ticks(small_lm):
    """Tick accounting: idle ticks advance the clock, admission and
    completion ticks land per request — the record the disaggregated
    cell pair (serving/cells.py) is diffed against."""
    cfg, params = small_lm
    eng = ServingEngine(cfg, params, slots=1, max_seq=32)
    eng.step()                                    # idle tick 0
    eng.submit(Request(rid=7, prompt=np.arange(3, dtype=np.int32),
                       max_new=3))
    while any(eng.active) or eng.waiting:
        eng.step()
    assert eng.admit_ticks == {7: 1}
    assert eng.completions == {7: 2}              # max(1, 3-1) decode steps
    assert eng.ticks == 3


def test_offload_sites_cover_arch_families():
    dense = decode_gemv_sites(ARCHS["qwen2-72b"])
    names = {s.name for s in dense}
    assert {"attn.wq", "attn.wo", "mlp.wo", "lm_head"} <= names
    moe = decode_gemv_sites(ARCHS["dbrx-132b"])
    assert any(s.name.startswith("moe.") for s in moe)
    ssm = decode_gemv_sites(ARCHS["mamba2-130m"])
    assert {"ssm.in_proj", "ssm.out_proj"} <= {s.name for s in ssm}
    assert not any(s.name.startswith("attn") for s in ssm)


def test_offload_planner_small_batch_wins():
    """PIM offload accelerates batch-1 decode; large batch favors host."""
    sim = PimSimulator()
    planner = OffloadPlanner(ARCHS["granite-8b"], sim)
    r1 = planner.decode_speedup(batch=1)
    r64 = planner.decode_speedup(batch=64)
    assert r1["speedup"] > 3.0, r1
    assert r1["offloaded"], "nothing offloaded at batch 1"
    assert r64["speedup"] <= r1["speedup"]


def test_occupancy_weighted_speedup_empty_histogram():
    """No decode steps observed -> neutral speedup 1.0 over 0 steps (the
    old 0/1e-9 guard collapsed to 0.0, reading as 'PIM infinitely bad')."""
    planner = OffloadPlanner(ARCHS["mamba2-130m"])
    tel = planner.occupancy_weighted_speedup({})
    assert tel == dict(steps=0, host_ns=0.0, mixed_ns=0.0, speedup=1.0,
                       per_batch_speedup={})


def test_offload_reshape_regime_for_moe():
    """granite-moe per-expert d_ff=512 < 2048 -> reshape engaged."""
    planner = OffloadPlanner(ARCHS["granite-moe-3b-a800m"])
    plan = planner.plan()
    small = [d for d in plan if d.site.h < 2048]
    assert small and all(d.reshape for d in small)


# ---------------------------------------------------------------------
# serve launcher: --smoke is a real switch; no hidden backend fallback
# ---------------------------------------------------------------------

@pytest.fixture
def tiny_arch(monkeypatch, tmp_path):
    """A smoke-sized architecture registered under its own name, so
    ``--no-smoke`` serves it "at its published widths" in seconds; the
    compile cache is pointed at an unset directory so the launcher turns
    on no persistent cache inside the test process."""
    import dataclasses

    from jax.experimental.compilation_cache import compilation_cache
    cfg = dataclasses.replace(smoke_config(ARCHS["gemma3-4b"]),
                              name="tiny-gemma")
    monkeypatch.setitem(ARCHS, "tiny-gemma", cfg)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield cfg
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_serve_no_smoke_serves_bf16_published_widths(tiny_arch):
    from repro.launch import serve
    out = serve.main(["--arch", "tiny-gemma", "--no-smoke",
                      "--requests", "3", "--slots", "2", "--max-new", "3"])
    assert out["cfg"] is tiny_arch
    assert {str(a.dtype) for a in jax.tree.leaves(out["params"])} == \
        {"bfloat16"}
    assert all(r.done and len(r.out) == 3 for r in out["requests"])
    smoke = serve.main(["--arch", "tiny-gemma", "--requests", "1",
                        "--max-new", "2"])
    assert smoke["cfg"] == smoke_config(tiny_arch)
    assert {str(a.dtype) for a in jax.tree.leaves(smoke["params"])} == \
        {"float32"}


def test_serve_pallas_request_fails_with_compiler_reason(tiny_arch,
                                                         monkeypatch):
    """Where the lane kernel cannot be built, ``--lane-backend pallas``
    raises the compiler's reason before serving anything."""
    from repro.kernels import lane_scan
    from repro.launch import serve

    def refuse(num_banks, unroll=None, interpret=None):
        raise NotImplementedError("Mosaic: no lowering for scan")

    lane_scan.require_lane_kernel.cache_clear()
    monkeypatch.setattr(lane_scan, "make_lane_resolver", refuse)
    try:
        with pytest.raises(NotImplementedError, match="Mosaic"):
            serve.main(["--arch", "tiny-gemma", "--lane-backend", "pallas",
                        "--requests", "1", "--max-new", "2"])
    finally:
        lane_scan.require_lane_kernel.cache_clear()
