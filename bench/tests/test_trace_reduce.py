import numpy as np

from trace_reduce import Busy


def test_busy_union_between_and_gaps():
    # overlapping and nested device intervals (ns)
    bz = Busy([0, 5, 20, 22, 40], [10, 8, 30, 25, 45])
    assert list(bz.s) == [0, 20, 40] and list(bz.e) == [10, 30, 45]
    assert bz.between(0, 50) == 25
    assert bz.between(5, 25) == 10            # 5..10 and 20..25
    assert np.allclose(bz.between(np.array([0, 12]), np.array([9, 19])),
                       [9, 0])
    assert bz.gaps(0, 50) == [(10, 20), (30, 40), (45, 50)]
    assert bz.gaps(25, 42) == [(30, 40)]


def test_self_time_of_nested_ops():
    from trace_reduce import Trace

    tr = Trace.__new__(Trace)
    tr.devices = [dict(ops=[("%while.1 = (s32[]) while(...)", 0, 100),
                            ("%fusion.2 = f32[] fusion(...)", 10, 30),
                            ("%fusion.2 = f32[] fusion(...)", 50, 20),
                            ("%copy.3 = f32[] copy(...)", 120, 5)])]
    top = dict(tr.top_ops(0, 200))
    assert top == {"%while.1": 50e-9, "%fusion.2": 50e-9,
                   "%copy.3": 5e-9}


def test_recorded_tpu_trace():
    """``data/tiny_trace.xplane.pb``: a TPU v5e trace of three
    ``bench.step`` spans, each one call of a jitted chain of four
    2048 x 2048 bf16 matmuls, 20 ms of host sleep after each, inside one
    ``bench.window``.  The expected values were read off the trace by
    hand: the device's events come out 1372436 ns before the host's
    enqueue of the same run; each call's operations cover 361028,
    361025 and 361024 ns."""
    import os

    from trace_reduce import Trace

    tr = Trace(os.path.join(os.path.dirname(__file__), "data",
                            "tiny_trace.xplane.pb"))
    assert len(tr.devices) == 1
    assert tr.devices[0]["shift_ns"] == 1372436
    a, b = tr.window()
    assert (a, b) == (49906459, 115569977)
    assert tr.busy_s(a, b) == 1083077e-9
    spans = tr.spans("bench.step")
    assert [int(s[2]["i"]) for s in spans] == [0, 1, 2]
    assert list(np.round(tr.span_device_s(spans) * 1e9)) == \
        [361028, 361025, 361024]
    assert tr.module_s("jit_f", a, b) == 1083121e-9
    idle = dict(tr.idle_gaps(a, b))
    assert abs(sum(idle.values()) - (b - a - 1083077) / 1e9) < 1e-12
    assert max(idle, key=idle.get) == "$time sleep"
