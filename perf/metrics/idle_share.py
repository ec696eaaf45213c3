"""Share of the traced window in which no operation ran on the device
(1 minus the union of busy intervals, averaged over the chips)."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
