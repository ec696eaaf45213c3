"""Host milliseconds per round in the control plane outside the jax
planner's device call: the program's ``fl.plan`` span (``fl/server.py``)
less its ``fl.plan.auction`` child (``core/planner.py``, up to the wait on
the plan), from ``repro.obs``'s record of the window."""

from perf import program as P


def read(ctx):
    return P.span_ms(ctx, "fl.plan", less="fl.plan.auction")
