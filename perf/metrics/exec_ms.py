"""Host milliseconds per round in the executor's ``run_round``, up to a
block on the new global params (data plane: ``fl/executors.py``)."""


def read(ctx):
    if not ctx["spans"].calls["exec"]:
        return None
    return 1e3 * ctx["spans"].seconds["exec"] / ctx["rounds"]
