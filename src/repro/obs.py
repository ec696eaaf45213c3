"""Spans and counters at the program's layer boundaries.

One recorder per process, like the profiler it feeds.  It is off by
default, and then costs one flag check: :func:`span` returns a shared no-op
object (no clock read, no ``TraceAnnotation``), :func:`count` returns at
once.  It records

* inside :func:`recording`, and
* in a communication round (:class:`fl_round`) that starts while a JAX
  profiler trace is being captured (``jax.profiler.trace`` / ``start_trace``
  or a remote capture), so a profile of a run carries the spans with no
  further switch.

While on, each span opens a ``jax.profiler.TraceAnnotation`` of its name
(the profiler's ``/host:CPU`` plane, on the clock of the device planes)
and keeps a :class:`Span` in memory, stamped with ``time.perf_counter_ns``.
The two clocks differ: the trace's times are offsets from its own start.
Each time recording turns on, the recorder opens an ``obs.anchor`` span,
which lands in both records and lines the two up.  Backend compiles are
counted by JAX's ``fun_name`` and by the innermost open span.
:func:`snapshot` hands everything over and clears it.

Spans nest on the thread that opens them; the FL round loop opens them all
on its own thread.

    with jax.profiler.trace(log_dir):
        with obs.recording():
            run_federated(...)
    record = obs.snapshot()
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import defaultdict
from typing import NamedTuple

import jax
import numpy as np

__all__ = ["Span", "Record", "span", "timed", "count", "enabled", "fl_round",
           "recording", "snapshot"]

ANCHOR = "obs.anchor"
ROUND = "fl.round"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    parent: str | None      # the enclosing span's name
    round: int | None       # the communication round it ran in
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    self_ns: int            # end - start, less the direct children's time


@dataclasses.dataclass
class Record:
    """What :func:`snapshot` returns: the spans in the order they closed,
    the counters, and the backend compiles by ``(fun_name, innermost
    span)``."""
    spans: list
    counters: dict
    compiles: dict

    def totals(self) -> dict:
        """``name -> (calls, total ns, self ns)`` over every span."""
        out: dict = defaultdict(lambda: [0, 0, 0])
        for s in self.spans:
            t = out[s.name]
            t[0] += 1
            t[1] += s.end_ns - s.start_ns
            t[2] += s.self_ns
        return {k: tuple(v) for k, v in out.items()}


class _Recorder:
    def __init__(self):
        self.on = False
        self.depth = 0              # open recording() blocks
        self.follow = False         # this round records for the profiler
        self.round = None
        self.stack: list = []       # open spans, innermost last
        self.spans: list = []
        self.counters: dict = defaultdict(int)
        self.device: dict = defaultdict(list)   # device arrays, unread
        self.compiles: dict = defaultdict(int)
        self.listening = False

    def update(self) -> None:
        was, self.on = self.on, self.depth > 0 or self.follow
        if self.on and not was:
            if not self.listening:
                jax.monitoring.register_event_duration_secs_listener(
                    self.on_event)
                self.listening = True
            with _Span(ANCHOR):
                pass

    def on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.on and event == _BACKEND_COMPILE:
            inner = self.stack[-1].name if self.stack else None
            self.compiles[(kwargs.get("fun_name"), inner)] += 1


_R = _Recorder()


class _Span:
    """A live span; with recording off at its start it only stamps the
    clock (see :func:`timed`)."""
    __slots__ = ("name", "live", "ann", "child_ns", "t0", "t1")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.live = _R.on
        if self.live:
            self.child_ns = 0
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
            _R.stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter_ns()
        if self.live:
            self.ann.__exit__(*exc)
            stack = _R.stack
            stack.pop()
            dur = self.t1 - self.t0
            parent = stack[-1] if stack else None
            if parent is not None:
                parent.child_ns += dur
            _R.spans.append(Span(self.name,
                                 parent.name if parent else None, _R.round,
                                 self.t0, self.t1, dur - self.child_ns))

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


_OFF = _Off()


def span(name: str):
    """A context manager spanning one stage; a shared no-op while off."""
    if not _R.on:
        return _OFF
    return _Span(name)


def timed(name: str) -> _Span:
    """:func:`span` for a stage whose length the caller reads (``.seconds``)
    whether or not recording is on."""
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``.  ``n`` may be a device array, whose
    sum counts: it is kept as it is (no device work, no compile, no wait)
    and read when the record is taken."""
    if _R.on:
        if isinstance(n, jax.Array):
            _R.device[name].append(n)
        else:
            _R.counters[name] += n


def enabled() -> bool:
    """Whether spans and counters record now: guard a count that costs
    something to compute."""
    return _R.on


class fl_round:
    """The span of communication round ``t`` (``fl.round``): every span
    opened in it carries ``t``.  Outside :func:`recording` the round
    records if a profiler trace is being captured as it starts."""
    __slots__ = ("t", "span")

    def __init__(self, t: int):
        self.t = int(t)

    def __enter__(self):
        _R.round = self.t
        _R.follow = jax.profiler.TraceAnnotation.is_enabled()
        _R.update()
        self.span = span(ROUND)
        self.span.__enter__()
        return self

    def __exit__(self, *exc):
        self.span.__exit__(*exc)
        _R.follow = False
        _R.round = None
        _R.update()


@contextlib.contextmanager
def recording():
    """Record spans, counters and compiles in this block."""
    _R.depth += 1
    _R.update()
    try:
        yield
    finally:
        _R.depth -= 1
        _R.update()


def snapshot() -> Record:
    """Everything recorded so far; the recorder starts empty again."""
    counters = dict(_R.counters)
    for k, arrays in _R.device.items():
        counters[k] = counters.get(k, 0) + sum(
            int(np.sum(a)) for a in jax.device_get(arrays))
    rec = Record(spans=_R.spans, counters=counters,
                 compiles=dict(_R.compiles))
    _R.spans = []
    _R.counters = defaultdict(int)
    _R.device = defaultdict(list)
    _R.compiles = defaultdict(int)
    return rec

