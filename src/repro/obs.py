"""Program spans: one in-memory recorder for the layer boundaries of the
serving engine and the simulator.

``span(name, **attrs)`` is a context manager.  A span is recorded when
the recorder is enabled (``enable()``), when a JAX profiler session is
tracing the host, or when it opens inside a recorded span on the same
thread: a tree of spans is recorded whole or not at all, decided at its
root.  Otherwise ``span`` returns one shared no-op object, formats
nothing and emits nothing.

A recorded span does two things.  It appends a :class:`Record` to an
in-memory list, on ``time.perf_counter_ns`` (the clock of the
benchmark's step and request records), with the innermost recorded
span of its thread as parent; and it enters
``jax.profiler.TraceAnnotation(name, **attrs)``, so that a profiled run
shows the same span on the profiler's host plane, on the device trace's
clock.  ``set(**attrs)`` adds counts known only at the end; they go into
the memory record only.  ``take()`` hands the records over and clears
them.

Call sites compute no attribute that costs more than a ``len()``, or
guard the cost with ``on()``.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple

import jax

_ids = itertools.count(1)
_local = threading.local()
_records: list["Record"] = []
_enabled = False
_profiling = jax.profiler.TraceAnnotation.is_enabled


class Record(NamedTuple):
    id: int
    parent: int               # 0: a root span
    name: str
    t0_ns: int
    t1_ns: int
    thread: int
    attrs: dict


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def on() -> bool:
    """Whether a span opened here now would be recorded."""
    return bool(_enabled or _profiling() or getattr(_local, "stack", None))


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def take() -> list[Record]:
    """The records so far, oldest first; clears them.  Records appended
    by another thread meanwhile are kept for the next call."""
    n = len(_records)
    out = _records[:n]
    del _records[:n]
    return out


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NOOP = _Noop()


class _Span:
    __slots__ = ("id", "parent", "name", "attrs", "t0", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.id = next(_ids)
        self.name, self.attrs = name, attrs

    def __enter__(self):
        st = _stack()
        self.parent = st[-1].id if st else 0
        st.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        _records.append(Record(self.id, self.parent, self.name, self.t0, t1,
                               threading.get_ident(), self.attrs))
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)


def span(name: str, **attrs):
    """A span around the ``with`` block (see the module docstring)."""
    return _Span(name, attrs) if on() else NOOP


def interval(name: str, t0_ns: int, **attrs) -> None:
    """Record a span that began at ``t0_ns`` (a ``perf_counter_ns``
    reading) and ends now, such as a wait in a queue, under the
    innermost recorded span of this thread.  Memory only: the profiler
    cannot open an event in the past."""
    st = _stack()
    _records.append(Record(next(_ids), st[-1].id if st else 0, name, t0_ns,
                           time.perf_counter_ns(), threading.get_ident(),
                           attrs))
