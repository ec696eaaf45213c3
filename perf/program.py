"""The program's own spans and counters (``repro.obs``), read once a run.

``repro.obs`` records every communication round that starts while a
profiler trace is being captured, so the window of a traced run carries
the program's spans (``fl.*``) and counters in memory.  The first
per-layer metric that reads them takes the snapshot and keeps it in
``ctx["program"]``, and the per-span table goes to standard error.  A
program without ``repro.obs``, or a run that recorded nothing, leaves
``None`` there, and every metric that reads it then reports nothing.
"""
from __future__ import annotations

import sys


def record(ctx):
    """The program's :class:`repro.obs.Record` of the window, or None."""
    if "program" not in ctx:
        ctx["program"] = _take()
        if ctx["program"] is not None:
            print_table(ctx["program"], ctx["rounds"])
    return ctx["program"]


def _take():
    try:
        from repro import obs
    except ImportError:
        return None
    rec = obs.snapshot()
    return rec if rec.spans else None


def span_ms(ctx, name: str, less: str | None = None) -> float | None:
    """Host ms a round in span ``name`` (less its child ``less``)."""
    rec = record(ctx)
    tot = rec.totals() if rec is not None else {}
    if name not in tot:
        return None
    ns = tot[name][1] - (tot[less][1] if less in tot else 0)
    return 1e-6 * ns / ctx["rounds"]


def print_table(rec, rounds: int) -> None:
    """Per span: calls, host ms and self ms a round; then the counters and
    the compiles, on standard error."""
    out = sys.stderr
    print(f"{'program span':28s} {'calls':>7s} {'host ms/r':>10s} "
          f"{'self ms/r':>10s}", file=out)
    for name, (calls, total, self_ns) in sorted(rec.totals().items()):
        print(f"{name:28s} {calls:7d} {1e-6 * total / rounds:10.3f} "
              f"{1e-6 * self_ns / rounds:10.3f}", file=out)
    for name, n in sorted(rec.counters.items()):
        print(f"counter {name}={n}", file=out)
    for (fun, inner), n in sorted(rec.compiles.items(), key=str):
        print(f"compile {fun} in={inner} n={n}", file=out)
