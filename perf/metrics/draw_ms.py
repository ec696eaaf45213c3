"""Host milliseconds per round assembling the client batches: the
program's ``fl.exec.draw`` span (``FleetExecutor._draw_session``: the
epochs drawn, padded, stacked over the slots and put on the device), from
``repro.obs``'s record of the window."""

from perf import program as P


def read(ctx):
    return P.span_ms(ctx, "fl.exec.draw")
