"""Run one benchmark cell on the chip and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by the names in ``BENCHMARK.json`` (see ``registry.py``).  The
run refuses anything but a TPU, with as many chips as the cell asks for.
Set-up (weights from the seed, compiles or compile-cache loads, warm-up
of every shape the cell uses) ends before the window; the window lasts
``--seconds``; then the outputs of the timed path are checked against the
plain reference, and the last line printed is::

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics.
Two options are for calibration only: ``--rate`` overrides an open-loop
mix's rate (the sweep that finds the knee), and ``--control`` puts the
check's control in the program's place (the reference at a lower
precision, or with a guarantee broken), so that the same comparison
has to report ``correct`` false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import registry  # noqa: E402


class Clock:
    """Set-up time, compiles inside the window, and the device memory peak
    read after the window."""

    def __init__(self, devices):
        from jax import monitoring

        self.devices = devices
        self.setup_s = None
        self.compiles = [0, 0]        # backend compiles, cache loads
        self._in_window = False
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self._in_window:
            if name.endswith("backend_compile_duration"):
                self.compiles[0] += 1
            elif "cache_retrieval" in name:
                self.compiles[1] += 1

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_START
        self._in_window = True

    def window_done(self):
        self._in_window = False
        print(f"compiles inside the window: {self.compiles[0]} "
              f"(+{self.compiles[1]} loaded from the compile cache)",
              file=sys.stderr)

    def memory_peak(self) -> int | None:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None


class Tracer:
    """Profiler around the window when ``--trace 1``; else nothing.  The
    Python tracer stays off: host spans come from ``TraceAnnotation`` and
    JAX's own trace events, which cost far less per step.

    A driver that sets ``sampled`` traces only a sample of the window
    instead: ``sample(seconds)`` starts the profiler at once and stops it
    ``seconds`` later from a timer thread, the sample inside its own
    ``bench.window`` span.  That is for programs whose device events come
    too fast for the profiler to keep a whole window (the resolver's scan
    records every iteration).  ``begin()`` and ``end()`` do the same from
    the caller's own thread, at points it chooses (a serving loop's step
    boundaries); the window's end stops a sample still running."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.sampled = False
        self._thread = None
        self._span = None       # the sample's bench.window, while it runs

    def _start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def sample(self, seconds: float):
        if not self.on or self._thread is not None:
            return
        import threading

        import jax

        self._start()

        def stop():
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(seconds)
            jax.profiler.stop_trace()

        self._thread = threading.Thread(target=stop, daemon=True)
        self._thread.start()

    def begin(self):
        if not self.on or self._thread is not None or self._span:
            return
        import jax

        self._start()
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def end(self):
        if not self._span:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = False
        jax.profiler.stop_trace()

    def __enter__(self):
        if self.on and not self.sampled:
            self._start()
        return self

    def __exit__(self, *exc):
        self.end()
        if self._thread is not None:
            self._thread.join()
        elif self.on and not self.sampled:
            import jax
            jax.profiler.stop_trace()
        return False


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    print(f"device: platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"bench: refusing to measure on {dev['platform']}:"
                         f" no TPU found")
    if dev["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{dev['count']}")
    return dev


def per_layer(bench, cell, res, tracer, dev) -> tuple[dict, dict, dict]:
    """Per-layer metrics, device busy/window and the breakdown, from the
    traced window."""
    from peaks import peaks
    from trace_reduce import Trace, find_xplane

    tr = Trace(find_xplane(tracer.dir))
    a, b = tr.window()
    run = types.SimpleNamespace(cell=cell, trace=tr, window_ns=(a, b),
                                window_s=(b - a) / 1e9, peaks=peaks(
                                    dev["kind"]), **res)
    busy = tr.busy_s(a, b)
    metrics = {}
    for m in registry.per_layer(bench, cell["name"]):
        value = registry.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    device = dict(busy_s=busy, window_s=run.window_s)
    breakdown = dict(device_ops=tr.top_ops(a, b), idle_gaps=tr.idle_gaps(
        a, b))
    return metrics, device, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    c = registry.config(bench, cell["config"])
    mix = registry.traffic(cell["traffic"])

    from repro.core import warmstart

    warmstart.enable_compilation_cache()
    dev = device_check(cell["chips"])
    import jax

    devices = jax.devices()[:cell["chips"]]
    clock = Clock(devices)
    tracer = Tracer(bool(args.trace))
    try:
        if mix["kind"] == "design_sweep":
            import sweep_driver
            res = sweep_driver.run(cell, c, mix, args.seed, args.seconds,
                                   tracer, clock, control=args.control)
        else:
            import serve_driver
            res = serve_driver.run(cell, c, mix, args.seed, args.seconds,
                                   tracer, clock, rate=args.rate,
                                   control=args.control)
        device = dict(platform=dev["platform"], kind=dev["kind"],
                      count=len(devices), memory_peak_bytes=res["memory"])
        out = dict(correct=res["correct"], attempted=res["attempted"],
                   failed=res["failed"])
        if args.trace:
            metrics, dev_trace, breakdown = per_layer(bench, cell, res,
                                                      tracer, dev)
            device.update(dev_trace)
        else:
            values = dict(res["e2e"], setup_s=clock.setup_s)
            metrics = {m["name"]: dict(value=values[m["name"]],
                                       unit=m["unit"])
                       for m in registry.end_to_end(bench, cell["name"])}
            breakdown = None
    finally:
        if tracer.dir:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    out.update(metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = res["checks"]
    for name, chk in res["checks"].items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
