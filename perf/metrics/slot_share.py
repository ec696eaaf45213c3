"""Share of the vmapped step's client slots that train: the program's
counters ``fl.exec.active_slot_steps`` over ``fl.exec.slot_steps``
(``FleetExecutor._draw_session``: the slots of a session times its padded
steps), from ``repro.obs``'s record of the window."""

from perf import program as P


def read(ctx):
    rec = P.record(ctx)
    if rec is None or not rec.counters.get("fl.exec.slot_steps"):
        return None
    return 100.0 * (rec.counters.get("fl.exec.active_slot_steps", 0)
                    / rec.counters["fl.exec.slot_steps"])
