"""Device milliseconds per round in the fleet's vmapped client step: the
program ``FleetExecutor`` jits from its per-client step ``one``."""

PROGRAM = r"^jit_one$"


def read(ctx):
    s = ctx["trace"].module_time(PROGRAM)
    return None if s is None else 1e3 * s / ctx["rounds"]
