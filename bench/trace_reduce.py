"""Reduce a JAX profiler trace to device busy time, per-span device time,
module time, the costliest device operations and the longest idle gaps.

The harness wraps its window in one host span (``bench.window``) and each
call into the program in another (``bench.step`` or ``bench.sweep``, with
an ``i`` stat that numbers it), with ``jax.profiler.TraceAnnotation``.
The profiler puts host and device events on one clock, but on a TPU the
device's events come out ~1.3 ms early: a program starts on the device
before the host has enqueued it.  Each device is shifted so that none of
its programs starts before the host's ``DoEnqueueProgram`` of the same
``run_id`` ends; the device time inside a span is then the busy time of
the device that falls between the span's ends.

* Busy time: the union of the intervals of the device's operations
  (line ``XLA Ops`` of each ``/device:`` plane that has operations or
  modules), clipped to the window, averaged over the devices.
* Module time: the summed durations of the events of line
  ``XLA Modules`` whose name holds a given string (a jitted function's
  name, e.g. ``run_one``).
* Device operations: self time (an operation's duration less that of
  the operations nested in it, e.g. a while loop's body) by short name.
* Idle time: the gaps of the busy union inside the window, each named by
  the innermost host event on the harness's thread that covers the gap's
  middle, summed by name.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(starts: np.ndarray, ends: np.ndarray):
    """Sorted disjoint intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], ends[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, dtype=bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], run_end[np.r_[idx[1:] - 1, s.size - 1]]


class Busy:
    """Busy time of one device as a step function of the clock."""

    def __init__(self, starts, ends):
        self.s, self.e = _union(np.asarray(starts, np.float64),
                                np.asarray(ends, np.float64))
        lengths = self.e - self.s
        self.before = np.concatenate([[0.0], np.cumsum(lengths)])

    def until(self, t):
        """Busy nanoseconds before time ``t`` (vectorised)."""
        t = np.asarray(t, np.float64)
        k = np.searchsorted(self.s, t, side="right") - 1
        kk = np.clip(k, 0, max(self.s.size - 1, 0))
        if self.s.size == 0:
            return np.zeros_like(t)
        part = np.clip(np.minimum(t, self.e[kk]) - self.s[kk], 0, None)
        return np.where(k < 0, 0.0, self.before[kk] + part)

    def between(self, a, b):
        return self.until(b) - self.until(a)

    def gaps(self, a: float, b: float) -> list[tuple[float, float]]:
        """Idle intervals inside ``[a, b]``."""
        out, cur = [], a
        lo = np.searchsorted(self.e, a, side="right")
        for s, e in zip(self.s[lo:], self.e[lo:]):
            if s >= b:
                break
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < b:
            out.append((cur, b))
        return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - stats are optional in a trace
        return {}


class Trace:
    """The parts of one profile the reduction needs, as numpy arrays."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.devices: list[dict] = []
        self.host_lines: list[dict] = []
        enqueued: dict[int, float] = {}       # run_id -> host enqueue end
        for plane in pd.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == "DoEnqueueProgram":
                            rid = _stats(e).get("run_id")
                            if rid is not None:
                                enqueued[int(rid)] = e.start_ns + \
                                    e.duration_ns
        for plane in pd.planes:
            if plane.name.startswith("/device:") and "CPU" not in plane.name:
                dev = dict(name=plane.name, ops=[], modules=[])
                lags = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        dev["ops"] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
                    elif line.name == MODULES_LINE:
                        for e in line.events:
                            dev["modules"].append((e.name, e.start_ns,
                                                   e.duration_ns))
                            rid = _stats(e).get("run_id")
                            if rid is not None and int(rid) in enqueued:
                                lags.append(e.start_ns
                                            - enqueued[int(rid)])
                shift = -min(lags) if lags and min(lags) < 0 else 0.0
                dev["shift_ns"] = shift
                for key in ("ops", "modules"):
                    dev[key] = [(n, st + shift, d) for n, st, d in dev[key]]
                if dev["ops"] or dev["modules"]:
                    self.devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = [(e.name, e.start_ns, e.duration_ns, e)
                           for e in line.events]
                    if any(n.startswith("bench.") for n, *_ in evs):
                        self.host_lines.append(dict(name=line.name,
                                                    events=evs))
        if not self.devices:
            raise ValueError(f"no device plane in {path}")
        self.busy = []
        for dev in self.devices:
            ops = dev["ops"]
            st = np.asarray([o[1] for o in ops], np.float64)
            en = st + np.asarray([o[2] for o in ops], np.float64)
            self.busy.append(Busy(st, en))

    def spans(self, name: str) -> list[tuple[float, float, dict]]:
        """Host spans of ``name``: (start_ns, end_ns, stats)."""
        out = []
        for line in self.host_lines:
            for n, s, d, ev in line["events"]:
                if n == name:
                    out.append((float(s), float(s + d), _stats(ev)))
        out.sort(key=lambda x: x[0])
        return out

    def window(self) -> tuple[float, float]:
        w = self.spans("bench.window")
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0][0], w[0][1]

    def busy_s(self, a: float, b: float) -> float:
        """Device busy seconds in ``[a, b]``, averaged over devices."""
        return float(np.mean([bz.between(a, b) for bz in self.busy])) / 1e9

    def span_device_s(self, spans) -> np.ndarray:
        """Device busy seconds inside each span (mean over devices)."""
        if not spans:
            return np.zeros(0)
        a = np.asarray([s[0] for s in spans])
        b = np.asarray([s[1] for s in spans])
        return np.mean([bz.between(a, b) for bz in self.busy], axis=0) / 1e9

    def module_s(self, needle: str, a: float, b: float) -> float:
        """Summed device seconds of modules whose name holds ``needle``
        and that start inside ``[a, b]``, over all devices."""
        tot = 0
        for dev in self.devices:
            for n, s, d in dev["modules"]:
                if needle in n and a <= s <= b:
                    tot += d
        return tot / 1e9

    def top_ops(self, a: float, b: float, k: int = 10) -> list:
        """The ``k`` device operations with the most self time in
        ``[a, b]``, seconds averaged over devices."""
        acc: dict[str, float] = defaultdict(float)
        for dev in self.devices:
            ops = sorted((s, -d, n) for n, s, d in dev["ops"]
                         if a <= s <= b)
            stack: list[list] = []      # [end, name, self ns]
            for s, neg_d, n in ops:
                while stack and stack[-1][0] <= s:
                    _, name, own = stack.pop()
                    acc[name] += own
                if stack:
                    stack[-1][2] -= min(-neg_d, stack[-1][0] - s)
                stack.append([s - neg_d, n.split(" = ")[0], -neg_d])
            for _, name, own in stack:
                acc[name] += own
        scale = 1e9 * len(self.devices)
        return sorted(([n, v / scale] for n, v in acc.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, a: float, b: float, k: int = 10) -> list:
        """Idle seconds of the first device in ``[a, b]``, by what the
        harness's thread was doing in each gap; the ``k`` largest.  Each
        gap is named by the innermost host event covering its middle:
        host events paint the gaps' middles, longest first, so a nested
        event overwrites its parent."""
        bz = self.busy[0]
        lo = np.searchsorted(bz.e, a, side="left")
        hi = np.searchsorted(bz.s, b, side="left")
        g0 = np.concatenate([[a], bz.e[lo:hi]])
        g1 = np.concatenate([bz.s[lo:hi], [b]])
        g0 = np.maximum(g0, a)
        g1 = np.minimum(g1, b)
        keep = g1 > g0
        g0, g1 = g0[keep], g1[keep]
        mid = 0.5 * (g0 + g1)
        label = np.full(mid.size, -1)
        host = [ev for line in self.host_lines for ev in line["events"]]
        names = sorted({h[0] for h in host})
        index = {n: i for i, n in enumerate(names)}
        for name, st, dur, _ in sorted(host, key=lambda h: -h[2]):
            i = np.searchsorted(mid, st, side="left")
            j = np.searchsorted(mid, st + dur, side="right")
            label[i:j] = index[name]
        acc: dict[str, float] = defaultdict(float)
        length = (g1 - g0) / 1e9
        for li in np.unique(label):
            name = names[li] if li >= 0 else "host: no span"
            acc[name] += float(length[label == li].sum())
        return sorted(([n, v] for n, v in acc.items()),
                      key=lambda x: -x[1])[:k]
