"""The per-layer readers of the program's spans, on synthetic runs."""
import sys
import types

import pytest

from registry import metric_reader
from repro.obs import Record
from trace_reduce import Busy, Trace

MS = 1_000_000


def _rec(i, name, t0, t1, parent=0, **attrs):
    return Record(i, parent, name, t0, t1, 1, attrs)


def _chat_run():
    # window [1 s, 2 s] on the host clock; two requests, three steps
    base = 1_000 * MS
    spans = [
        _rec(1, "serve.queue", base + 10 * MS, base + 30 * MS, rid=0),
        _rec(2, "serve.queue", base + 20 * MS, base + 60 * MS, rid=1),
        _rec(3, "serve.queue", base + 900 * MS, base + 1_100 * MS, rid=2),
        _rec(4, "serve.admit", base + 30 * MS, base + 100 * MS, rid=0),
        _rec(5, "serve.admit", base + 60 * MS, base + 160 * MS, rid=1),
        _rec(6, "serve.plan", base + 200 * MS, base + 200 * MS + 40_000),
        _rec(7, "serve.plan", base + 300 * MS, base + 300 * MS + 60_000),
        _rec(8, "serve.plan", base + 400 * MS, base + 400 * MS + 90_000),
    ]
    return types.SimpleNamespace(window=(1.0, 2.0), spans=spans)


@pytest.mark.parametrize("name,want", [
    ("queue_wait_ms.chat", 30.0),          # 20 and 40 ms; rid 2 is late
    ("admit_ms.chat", 85.0),               # 70 and 100 ms
    ("planner_us.serve", 60.0),
])
def test_chat_span_readers(name, want):
    assert metric_reader(name)(_chat_run()) == pytest.approx(want)


def _sweep_run(commands=(1000, 3000)):
    # two sweeps, only the second one recorded; one slab resolve is
    # outside the window's sweeps (the check's run after the window)
    s0, s1 = 10 * 1_000 * MS, 20 * 1_000 * MS
    spans = [
        _rec(1, "sim.run_many", s1 + 1 * MS, s1 + 900 * MS),
        _rec(2, "sim.plan", s1 + 2 * MS, s1 + 402 * MS),
        _rec(3, "sim.lookup", s1 + 402 * MS, s1 + 452 * MS),
        _rec(4, "sim.pack", s1 + 452 * MS, s1 + 462 * MS),
        _rec(5, "sim.pack", s1 + 462 * MS, s1 + 467 * MS),
        _rec(6, "sim.resolve", s1 + 467 * MS, s1 + 471 * MS,
             commands=commands[0]),
        _rec(7, "sim.resolve", s1 + 471 * MS, s1 + 479 * MS,
             commands=commands[1]),
        _rec(8, "sim.resolve", 40_000 * MS, 40_001 * MS, commands=7),
    ]
    sweeps = [dict(t0=s0 / 1e9, t1=(s0 + 950 * MS) / 1e9),
              dict(t0=s1 / 1e9, t1=(s1 + 950 * MS) / 1e9)]
    return types.SimpleNamespace(window=(s0 / 1e9, (s1 + 960 * MS) / 1e9),
                                 sweeps=sweeps, spans=spans)


@pytest.mark.parametrize("name,want", [
    ("plan_ms.sim", 400.0),
    ("lane_prep_ms.sim", 65.0),                # 50 + 10 + 5
    ("resolver_ns_per_cmd.sim", 12 * MS / 4000),
])
def test_sweep_span_readers(name, want):
    assert metric_reader(name)(_sweep_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "queue_wait_ms.chat", "admit_ms.chat", "planner_us.serve",
    "plan_ms.sim", "lane_prep_ms.sim", "resolver_ns_per_cmd.sim"])
def test_span_readers_without_spans(name, monkeypatch):
    """A program that predates the recorder (no ``repro.obs``) gives
    no spans: every reader returns None and none raises."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    run = types.SimpleNamespace(window=(1.0, 2.0), sweeps=[
        dict(t0=1.0, t1=1.5)])
    assert metric_reader(name)(run) is None
    assert run.spans == []
    run = _sweep_run(commands=(0, 0))
    if name == "resolver_ns_per_cmd.sim":
        assert metric_reader(name)(run) is None


def _ev(name, start, dur):
    return (name, start, dur, None)


def _trace(modules, host, ops):
    tr = Trace.__new__(Trace)
    tr.devices = [dict(name="/device:TPU:0", ops=ops, modules=modules)]
    tr.host_lines = [dict(name="main", events=host)]
    tr.busy = [Busy([s for _, s, _ in ops], [s + d for _, s, d in ops])]
    return tr


def test_decode_trace_readers():
    # three steps: decode-only, admit + decode, decode-only
    host = [_ev("bench.window", 0, 100 * MS),
            _ev("serve.step", 10 * MS, 10 * MS),
            _ev("serve.decode", 11 * MS, 1 * MS),
            _ev("serve.step", 30 * MS, 20 * MS),
            _ev("serve.admit", 31 * MS, 8 * MS),
            _ev("serve.decode", 40 * MS, 1 * MS),
            _ev("serve.step", 60 * MS, 12 * MS),
            _ev("serve.decode", 61 * MS, 1 * MS),
            _ev("serve.step", 80 * MS, 3 * MS),      # idle tick
            _ev("serve.step", 85 * MS, 10 * MS),     # its device events
            _ev("serve.decode", 86 * MS, 1 * MS)]    # were not kept
    ops = [("fusion.1", 12 * MS, 7 * MS),            # 7 of step 1's 10
           ("fusion.2", 32 * MS, 15 * MS),
           ("fusion.3", 62 * MS, 8 * MS)]            # 8 of step 3's 12
    modules = [("jit_decode_step(17)", 12 * MS, 7 * MS),
               ("jit_prefill(3)", 32 * MS, 6 * MS),
               ("jit_decode_step(17)", 41 * MS, 6 * MS),
               ("jit_decode_step(17)", 62 * MS, 8 * MS),
               ("jit_decode_step(17)", 200 * MS, 1 * MS)]   # after window
    run = types.SimpleNamespace(trace=_trace(modules, host, ops),
                                window_ns=(0, 100 * MS))
    assert metric_reader("decode_device_ms.serve")(run) == \
        pytest.approx(7.0)                           # of 7, 6, 8
    assert metric_reader("decode_host_ms.serve")(run) == \
        pytest.approx(3.5)                           # of 3 and 4, not 10
    old = types.SimpleNamespace(                     # no named module,
        trace=_trace([("jit__lambda_(1)", 12 * MS, 7 * MS)],   # no spans
                     host[:1], ops), window_ns=(0, 100 * MS))
    assert metric_reader("decode_device_ms.serve")(old) is None
    assert metric_reader("decode_host_ms.serve")(old) is None


def test_decode_roofline_counts_only_steps_whose_device_events_were_kept():
    import tiny
    from counts import decode_step_bytes

    def step(i, start, dur):
        stats = types.SimpleNamespace(stats=[("i", i)])
        return ("bench.step", start, dur, stats)

    host = [_ev("bench.window", 0, 100 * MS), step(0, 10 * MS, 10 * MS),
            step(1, 30 * MS, 10 * MS), step(2, 50 * MS, 10 * MS)]
    ops = [("fusion.1", 11 * MS, 8 * MS), ("fusion.2", 51 * MS, 4 * MS)]
    modules = [("jit_decode_step(17)", 11 * MS, 8 * MS),
               ("jit_decode_step(17)", 51 * MS, 4 * MS)]
    steps = [dict(i=i, admit=False, decode_ctx=[100 * (i + 1), 50])
             for i in range(3)]             # step 1's events were dropped
    run = types.SimpleNamespace(trace=_trace(modules, host, ops),
                                window_ns=(0, 100 * MS), steps=steps,
                                config=tiny.TINY,
                                peaks=dict(hbm_bytes_per_s=1e12))
    least = (decode_step_bytes(tiny.TINY, [100, 50])
             + decode_step_bytes(tiny.TINY, [300, 50])) / 1e12
    assert metric_reader("decode_roofline.serve")(run) == \
        pytest.approx(100 * least / 0.012)
