"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<i>``.  On each, the ``XLA Ops`` line holds one event per
operation run (its name is the HLO instruction's text), and the ``XLA
Modules`` line one event per program run (named after the jitted program,
e.g. ``jit_one(123)``).  Host threads are lines of the ``/host:CPU``
plane, where the harness's ``TraceAnnotation`` spans appear by name.  All
events of one file share one clock (``start_ns``, ``duration_ns``).

What comes out (:class:`Reduced`): per device the union of busy intervals,
device time by program and by operation, the idle gaps labelled by the
innermost harness span open at each gap's midpoint, and the window the
trace covers (first to last event of the device or the spans).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPANS = ("plan", "exec", "batch", "eval")


@dataclasses.dataclass
class Events:
    """One line's events: names and [start, end) in ns."""
    names: list
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, triples) -> "Events":
        triples = list(triples)
        return cls([t[0] for t in triples],
                   np.array([t[1] for t in triples], np.int64),
                   np.array([t[1] + t[2] for t in triples], np.int64))


def _module_name(name: str) -> str:
    """``jit_one(123)`` -> ``jit_one``."""
    return re.sub(r"\(\d+\)$", "", name)


@functools.lru_cache(maxsize=None)
def _op_name(name: str) -> str:
    """An HLO instruction's text -> its name and kind:
    ``%fusion.7 = f32[...] fusion(...), kind=...`` -> ``%fusion.7 fusion``."""
    head, _, rest = name.partition(" = ")
    kind = re.search(r"(?:^|[\s}])([a-z][\w\-]*)\(", rest)
    return f"{head} {kind.group(1)}" if kind else head


def gaps_ns(start: np.ndarray, end: np.ndarray, lo: int, hi: int
            ) -> tuple[np.ndarray, np.ndarray]:
    """The idle stretches of [lo, hi] that no [start, end) covers, as
    (gap starts, gap ends)."""
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    covered = np.maximum.accumulate(np.concatenate([[lo], e]))
    g0 = covered[:-1]                  # busy up to here before interval i
    g1 = np.minimum(s, hi)
    keep = g1 > g0
    g0, g1 = list(g0[keep]), list(g1[keep])
    if covered[-1] < hi:
        g0.append(covered[-1])
        g1.append(hi)
    return np.array(g0, np.int64), np.array(g1, np.int64)


def union_ns(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by a set of (start, end) intervals."""
    if not intervals:
        return 0
    s = np.array([i[0] for i in intervals], np.int64)
    e = np.array([i[1] for i in intervals], np.int64)
    lo, hi = int(s.min()), int(e.max())
    g0, g1 = gaps_ns(s, e, lo, hi)
    return hi - lo - int(np.sum(g1 - g0))


def label_gaps(mids: np.ndarray, spans: dict) -> list[str]:
    """For each midpoint, the innermost harness span open there.  Spans of
    one name never overlap (one host thread opens them in turn)."""
    best = np.full(len(mids), np.iinfo(np.int64).max)
    label = np.full(len(mids), "outside spans", dtype=object)
    for name, ev in spans.items():
        order = np.argsort(ev.start)
        s, e = ev.start[order], ev.end[order]
        i = np.searchsorted(s, mids, side="right") - 1
        ok = i >= 0
        ok[ok] &= e[i[ok]] > mids[ok]
        dur = np.where(ok, e[np.maximum(i, 0)] - s[np.maximum(i, 0)],
                       np.iinfo(np.int64).max)
        inner = dur < best
        best = np.where(inner, dur, best)
        label[inner] = name
    return list(label)


@dataclasses.dataclass
class Reduced:
    devices: int
    window_s: float                       # length the trace covers
    busy_s: float                         # busy union, averaged over chips
    module_s: dict                        # program -> device s (all chips)
    op_s: dict                            # operation -> device s (all chips)
    gap_s: dict                           # span label -> idle s (all chips)
    span_s: dict                          # harness span -> host s

    def module_time(self, pattern: str) -> float | None:
        """Device seconds of the programs whose name matches, per chip."""
        rx = re.compile(pattern)
        hits = [v for k, v in self.module_s.items() if rx.search(k)]
        return sum(hits) / self.devices if hits else None

    def op_time(self, pattern: str) -> float | None:
        rx = re.compile(pattern)
        hits = [v for k, v in self.op_s.items() if rx.search(k)]
        return sum(hits) / self.devices if hits else None

    def breakdown(self) -> dict:
        """The ten operations that took most device time and the idle
        time by the host span open, per chip."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v / self.devices] for k, v in ops],
                "idle_gaps": [[k, v / self.devices] for k, v in gaps]}


def reduce_planes(planes, devices: int) -> Reduced:
    """Reduce the planes of one trace file (``ProfileData.planes``)."""
    dev_ops: dict = {}
    dev_mods: dict = {}
    spans: dict = defaultdict(list)
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < devices:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops[int(m.group(1))] = Events.of(
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events)
                elif line.name == MODULES_LINE:
                    dev_mods[int(m.group(1))] = Events.of(
                        (_module_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)) for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans[ev.name].append((ev.name, int(ev.start_ns),
                                               int(ev.duration_ns)))
    return reduce_events(dev_ops, dev_mods,
                         {k: Events.of(v) for k, v in spans.items()}, devices)


def _by_name(ev: Events, rename=lambda n: n) -> dict:
    out: dict = defaultdict(float)
    for name, d in zip(ev.names, (ev.end - ev.start).tolist()):
        out[rename(name)] += d * 1e-9
    return out


def reduce_events(dev_ops: dict, dev_mods: dict, spans: dict,
                  devices: int) -> Reduced:
    """``dev_ops``/``dev_mods``: device index -> :class:`Events`;
    ``spans``: span name -> :class:`Events`."""
    if not dev_ops or not any(len(ev.start) for ev in dev_ops.values()):
        raise ValueError("the trace holds no device operation")
    edges = [(int(ev.start.min()), int(ev.end.max()))
             for ev in list(dev_ops.values()) + list(spans.values())
             if len(ev.start)]
    lo, hi = min(e[0] for e in edges), max(e[1] for e in edges)
    busy = 0
    op_s: dict = defaultdict(float)
    module_s: dict = defaultdict(float)
    gap_s: dict = defaultdict(float)
    for ev in dev_ops.values():
        g0, g1 = gaps_ns(ev.start, ev.end, lo, hi)
        busy += hi - lo - int(np.sum(g1 - g0))
        for label, d in zip(label_gaps((g0 + g1) // 2, spans),
                            ((g1 - g0) * 1e-9).tolist()):
            gap_s[label] += d
        for k, v in _by_name(ev, _op_name).items():
            op_s[k] += v
    for ev in dev_mods.values():
        for k, v in _by_name(ev).items():
            module_s[k] += v
    span_s = {k: float(np.sum(ev.end - ev.start)) * 1e-9
              for k, ev in spans.items()}
    return Reduced(devices=devices, window_s=(hi - lo) * 1e-9,
                   busy_s=busy * 1e-9 / devices, module_s=dict(module_s),
                   op_s=dict(op_s), gap_s=dict(gap_s), span_s=span_s)


def reduce_file(path: str, devices: int) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         devices)


def reduce_dir(trace_dir: str, devices: int) -> Reduced:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file, found {files}")
    return reduce_file(files[0], devices)
