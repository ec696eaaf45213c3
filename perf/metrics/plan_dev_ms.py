"""Device milliseconds per round in the jax planner's program: the
bid/auction/schedule ``while_loop`` that ``core/planner.py`` jits from
``_plan_rounds``."""

PROGRAM = r"^jit__plan_rounds$"


def read(ctx):
    s = ctx["trace"].module_time(PROGRAM)
    return None if s is None else 1e3 * s / ctx["rounds"]
