"""The rest of a run, with the timed path broken underneath: ``correct``
has to come out false for each fault a cell can have.  No chip: the
harness's device check is skipped and the drivers run on the CPU at a
tiny size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny


def test_unbroken_serve_run_is_correct():
    res = tiny.serve()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 10


def _broken_decode(monkeypatch, fault):
    from repro.models import model as M

    real = M.decode_step

    def decode_step(cfg, params, cache, token, pos):
        logits, new = real(cfg, params, cache, token, pos)
        if fault == "state_unchanged":
            return logits, cache
        if fault == "half_batch":
            h = logits.shape[0] // 2
            return jnp.concatenate([logits[:h], logits[:h]], 0), new
        return jnp.roll(logits, 1, axis=-1), new         # token altered

    monkeypatch.setattr(M, "decode_step", decode_step)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
@pytest.mark.parametrize("mix,cfg", [("open_loop", "TINY"),
                                     ("offline", "TINY"),
                                     ("offline", "TINY_P99")])
def test_broken_serve_run_is_not_correct(monkeypatch, fault, mix, cfg):
    _broken_decode(monkeypatch, fault)
    res = tiny.serve(cfg=getattr(tiny, cfg),
                     mix=tiny.OPEN if mix == "open_loop" else tiny.OFFLINE)
    assert not res["correct"], res["checks"]


def test_unbroken_sweep_run_is_correct():
    res = tiny.sweep()
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged", "commands_dropped"])
def test_broken_sweep_run_is_not_correct(monkeypatch, fault):
    from repro.core import engine
    from repro.pimkernel.executor import PimExecutor

    if fault == "commands_dropped":
        # the planner loses the last command of every stream; the
        # reference then resolves the same short streams
        real_plan = PimExecutor.plan_many

        def plan_many(self, reqs):
            out = real_plan(self, reqs)
            for p in out:
                p.streams = [s[:-1] for s in p.streams]
                if p.gs is not None:
                    p.gs.streams = p.streams
                p.stream_keys = [("dropped",) + tuple(k) if isinstance(
                    k, tuple) else k for k in p.stream_keys]
            return out

        monkeypatch.setattr(PimExecutor, "plan_many", plan_many)
        engine.lane_cache_reset()
        try:
            res = tiny.sweep()
        finally:
            engine.lane_cache_reset()
        assert not res["correct"], res["checks"]
        assert res["checks"]["mismatched_answers"]["value"] == 0
        return

    if fault == "state_unchanged":
        real_build = engine._build_step

        def build_step(nb):
            step = real_build(nb)
            return lambda c, st, cmd: (st, step(c, st, cmd)[1])

        monkeypatch.setattr(engine, "_build_step", build_step)
        engine._RESOLVERS.clear()
        engine.lane_cache_reset()
        try:
            res = tiny.sweep()
        finally:
            engine._RESOLVERS.clear()
            engine.lane_cache_reset()
        assert not res["correct"], res["checks"]
        return
    real = engine.resolve_fleet

    def resolve_fleet(points, *a, **kw):
        out = real(points, *a, **kw)
        for i, fr in enumerate(out):
            if fault == "answer_altered" and i == 0:
                fr.totals = fr.totals + 1
            if fault == "half_batch" and i % 2:
                fr.totals = np.zeros_like(fr.totals)
        return out

    monkeypatch.setattr(engine, "resolve_fleet", resolve_fleet)
    res = tiny.sweep()
    assert not res["correct"], res["checks"]
