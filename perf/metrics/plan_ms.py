"""Host milliseconds per round in the strategy's scheduler (control plane:
``fl/schedulers.py`` and the jax planner's device calls it makes)."""


def read(ctx):
    if not ctx["spans"].calls["plan"]:
        return None
    return 1e3 * ctx["spans"].seconds["plan"] / ctx["rounds"]
