"""Program spans (``repro.obs``): the recorder itself, its profiler view,
and the spans of the serving engine and of the simulator's resolve path.
"""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCHS, smoke_config
from repro.core import engine
from repro.models import model as M


@pytest.fixture(autouse=True)
def _clean_recorder():
    obs.disable()
    obs.take()
    yield
    obs.disable()
    obs.take()


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


def test_off_returns_the_shared_noop_and_records_nothing():
    assert not obs.on()
    sp = obs.span("x", n=1)
    assert sp is obs.NOOP
    with obs.span("a") as a:
        a.set(k=2)
        with obs.span("b"):
            pass
    obs.interval("q", time.perf_counter_ns())   # explicit: recorded
    assert [r.name for r in obs.take()] == ["q"]


def test_nesting_parents_and_set_attrs():
    obs.enable()
    with obs.span("outer", n=3) as o:
        with obs.span("inner") as i:
            i.set(hits=5)
        o.set(done=1)
    obs.disable()
    # a span that opens inside a recorded one is recorded with it
    with obs.span("root_off"):
        pass
    recs = _by_name(obs.take())
    assert set(recs) == {"outer", "inner"}
    (outer,), (inner,) = recs["outer"], recs["inner"]
    assert outer.parent == 0 and inner.parent == outer.id
    assert outer.attrs == dict(n=3, done=1) and inner.attrs == dict(hits=5)
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert obs.take() == []


def test_children_of_a_recorded_root_record_after_disable():
    obs.enable()
    with obs.span("root") as r:
        obs.disable()
        assert obs.on()
        with obs.span("child"):
            pass
    assert not obs.on()
    recs = _by_name(obs.take())
    assert recs["child"][0].parent == recs["root"][0].id
    assert r is not obs.NOOP


def test_per_thread_stacks_stay_separate():
    obs.enable()
    barrier = threading.Barrier(2, timeout=10)

    def work(tag):
        with obs.span("t", tag=tag):
            barrier.wait()              # both roots open at once
            with obs.span("c", tag=tag):
                barrier.wait()

    threads = [threading.Thread(target=work, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    recs = _by_name(obs.take())
    roots = {r.attrs["tag"]: r for r in recs["t"]}
    assert all(r.parent == 0 for r in roots.values())
    for c in recs["c"]:
        root = roots[c.attrs["tag"]]
        assert c.parent == root.id and c.thread == root.thread
    assert roots[0].thread != roots[1].thread


def test_span_lands_in_the_profilers_host_plane(tmp_path):
    """Under the profiler a span is recorded without ``enable()`` and
    lands in the ``.xplane.pb`` host plane with the same length."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert obs.on()
        with obs.span("obs.test", k=7):
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    assert not obs.on()
    (rec,) = obs.take()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    events = [e for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name == "obs.test"]
    assert len(events) == 1
    mem = rec.t1_ns - rec.t0_ns
    assert abs(events[0].duration_ns - mem) <= 0.05 * mem


class _StubPlanner:
    def plan(self, fence=True, spec=None):
        return []

    def invalidate(self):
        pass


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config(ARCHS["granite-8b"])
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def test_serving_engine_spans(small_lm):
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.policy import OffloadController

    cfg, params = small_lm
    eng = ServingEngine(cfg, params, slots=2, max_seq=32,
                        controller=OffloadController(_StubPlanner(),
                                                     policy="per-step"))
    rng = np.random.default_rng(0)
    obs.enable()
    reqs = [Request(rid=10 + i, prompt=rng.integers(0, cfg.vocab, 4 + i),
                    max_new=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    while eng.waiting or any(eng.active):
        eng.step()
    obs.disable()
    recs = _by_name(obs.take())
    rids = sorted(r.rid for r in reqs)
    assert sorted(r.attrs["rid"] for r in recs["serve.queue"]) == rids
    assert sorted(r.attrs["rid"] for r in recs["serve.admit"]) == rids
    admits = {r.id: r for r in recs["serve.admit"]}
    for name in ("serve.prefill", "serve.merge", "serve.first_token"):
        assert len(recs[name]) == 3
        assert all(r.parent in admits for r in recs[name])
    steps = {r.id: r for r in recs["serve.step"]}
    assert len(steps) == eng.ticks
    assert [s.attrs["batch"] for s in steps.values()] == eng.step_batches
    for name in ("serve.h2d", "serve.decode", "serve.sample",
                 "serve.readback", "serve.plan"):
        assert len(recs[name]) == len(eng.step_batches), name
        assert all(r.parent in steps for r in recs[name])
    assert all(r.parent in steps for r in recs["serve.queue"])
    assert all(r.attrs["offloaded"] == 0 for r in recs["serve.plan"])
    for q in recs["serve.queue"]:
        (adm,) = [a for a in admits.values()
                  if a.attrs["rid"] == q.attrs["rid"]]
        assert q.t1_ns <= adm.t0_ns


def _requests():
    from repro.core.timing import LpddrTimings, SystemSpec
    from repro.pimkernel.executor import GemvRequest
    from repro.pimkernel.tileconfig import PimDType

    slow = SystemSpec(timings=LpddrTimings(tRCD=24.0))
    return [GemvRequest.pim(256, 512, PimDType.W8A8, fence=True),
            GemvRequest.pim(256, 512, PimDType.W8A8, spec=slow),
            GemvRequest.baseline(256, 512, PimDType.W8A8),
            GemvRequest.pim(256, 512, PimDType.W8A8, fence=True)]


@pytest.mark.parametrize("rung", ["scan", "mesh"])
def test_run_many_spans(rung):
    import repro.core  # noqa: F401 - before repro.pimkernel (import cycle)
    from repro.pimkernel.executor import PimExecutor

    reqs = _requests()
    engine.lane_cache_clear()
    obs.enable()
    with engine.lane_mesh_scope(1 if rung == "mesh" else None):
        assert engine.ladder_rungs()[0] == rung
        PimExecutor().run_many(reqs)
    obs.disable()
    recs = _by_name(obs.take())
    (run,) = recs["sim.run_many"]
    assert run.attrs == dict(requests=4, unique=3)
    (plan,) = recs["sim.plan"]
    (look,) = recs["sim.lookup"]
    assert plan.parent == run.id
    assert recs["sim.pack"] and len(recs["sim.pack"]) == \
        len(recs["sim.resolve"])
    # the true lengths of the lanes resolved: one per distinct
    # (timing config, stream bytes) of the planned streams
    planned = PimExecutor().plan_many(reqs[:3])
    want = {(p.ctx.cyc, st.tobytes()): len(st)
            for p in planned for st in p.streams}
    assert plan.attrs == dict(
        streams=sum(len(p.streams) for p in planned),
        commands=sum(len(st) for p in planned for st in p.streams))
    assert look.attrs["lanes"] == plan.attrs["streams"]
    assert look.attrs["misses"] == look.attrs["unique"]
    assert look.attrs["hits"] == 0 and look.attrs["todo"] == len(want)
    assert sum(r.attrs["commands"] for r in recs["sim.resolve"]) == \
        sum(want.values())
    for r in recs["sim.pack"]:
        assert r.attrs["width"] >= 1 and r.attrs["length"] >= 1

    # warm: every lane hits the LRU, nothing is packed or resolved
    obs.enable()
    PimExecutor().run_many(reqs)
    obs.disable()
    recs = _by_name(obs.take())
    (look,) = recs["sim.lookup"]
    assert look.attrs["hits"] == look.attrs["unique"]
    assert look.attrs["todo"] == 0
    assert "sim.pack" not in recs and "sim.resolve" not in recs


@pytest.mark.parametrize("which", ["decode_step", "prefill"])
def test_jitted_serving_functions_are_named(small_lm, which):
    cfg, params = small_lm
    cache = M.init_cache(cfg, 2, 16, jnp.float32)
    if which == "decode_step":
        lowered = M.jit_decode_step(cfg).lower(
            params, cache, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32))
    else:
        cache = M.init_cache(cfg, 1, 16, jnp.float32)
        lowered = M.jit_prefill(cfg).lower(
            params, {"tokens": jnp.zeros((1, 5), jnp.int32)}, cache)
    assert f"jit_{which}" in lowered.as_text().splitlines()[0]
