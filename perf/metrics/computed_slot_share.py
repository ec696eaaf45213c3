"""Share of the client slots the vmapped step computes that train: the
program's counters ``fl.exec.active_slot_steps`` over
``fl.exec.computed_slot_steps`` (``FleetExecutor._draw_session``: the
slots a session stacks times its padded steps), from ``repro.obs``'s
record of the window.  A program that computes every slot of the fleet
has no such counter, and reads its ``fl.exec.slot_steps`` instead."""

from perf import program as P


def read(ctx):
    rec = P.record(ctx)
    if rec is None:
        return None
    counters = rec.counters
    computed = (counters.get("fl.exec.computed_slot_steps")
                or counters.get("fl.exec.slot_steps"))
    if not computed:
        return None
    return 100.0 * counters.get("fl.exec.active_slot_steps", 0) / computed
