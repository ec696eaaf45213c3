"""The program's spans (``repro.obs``, named ``fl.*``) in a profiler trace,
joined with the device's idle time.

    python3 perf/spans.py --workload <name> --seed <n> [--seconds 10]

runs one cell as a traced ``perf/run.py`` run does (the same set-up, warm-up
and window), reduces the trace and prints, per program span and per round
of the window: calls, host ms, self ms (less the spans it opened) and the
device's idle ms while it was the innermost open program span; then the
program's counters and its compiles in the window.  Off a TPU it refuses to
run, as the benchmark does.

:func:`reduce_planes` is the reduction, on ``devtrace``'s busy and gap
arithmetic; ``devtrace`` itself labels gaps by the harness's own spans.
"""
from __future__ import annotations

import argparse
import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
ROOT = os.path.dirname(HERE)

PREFIX = "fl."


def self_ns(spans: dict) -> dict:
    """Self ns per span name: each event's length less the events directly
    inside it.  ``spans``: name -> ``devtrace.Events``, all opened by one
    thread, so they nest."""
    events = sorted(((int(s), int(e), name) for name, ev in spans.items()
                     for s, e in zip(ev.start, ev.end)),
                    key=lambda t: (t[0], -t[1]))
    out: dict = {name: 0 for name in spans}
    stack: list = []                    # [end, name, child ns]
    for s, e, name in events:
        while stack and stack[-1][0] <= s:
            end, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([e, name, 0])
    for end, n, child in stack:
        out[n] -= child
    return out


def reduce_planes(planes, devices: int) -> dict:
    """Per program span: ``calls``, ``host_s``, ``self_s`` and ``idle_s``
    (device idle, summed over the chips, while it was the innermost open
    program span; ``outside spans`` for the rest of the window)."""
    from perf import devtrace as T
    dev_ops: dict = {}
    host: dict = {}
    for plane in planes:
        m = T.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < devices:
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    dev_ops[int(m.group(1))] = T.Events.of(
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events)
        elif plane.name == T.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host.setdefault(ev.name, []).append(
                            (ev.name, int(ev.start_ns), int(ev.duration_ns)))
    spans = {k: T.Events.of(v) for k, v in host.items()}
    return reduce_events(dev_ops, spans, devices)


def reduce_events(dev_ops: dict, spans: dict, devices: int) -> dict:
    """:func:`reduce_planes` on events: ``dev_ops`` device index ->
    ``Events``, ``spans`` program span name -> ``Events``."""
    from perf import devtrace as T
    red = T.reduce_events(dev_ops, {}, spans, devices)
    own = self_ns(spans)
    table = {name: {"calls": len(ev.start), "host_s": red.span_s[name],
                    "self_s": own[name] * 1e-9,
                    "idle_s": red.gap_s.get(name, 0.0)}
             for name, ev in spans.items()}
    table["outside spans"] = {"calls": 0, "host_s": 0.0, "self_s": 0.0,
                              "idle_s": red.gap_s.get("outside spans", 0.0)}
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from perf import harness as H
    from perf import program as P
    from perf import run as RUN
    from perf.traffic.generate import load_mix

    cell = RUN.find_cell(RUN.manifest(), args.workload)
    RUN.enable_compile_cache()
    devices = RUN.accelerator(int(cell["chips"]))
    if devices is None:
        return 2
    conf, ref, glue = H.load_config(cell["config"])
    with H.trace_dir_for(True) as tdir:
        rec, _, _, _ = H.drive(conf, ref, glue, load_mix(cell["traffic"]),
                               args.seed, args.seconds, tdir)
        path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        table = reduce_planes(
            jax.profiler.ProfileData.from_file(path).planes, len(devices))
    rounds = rec.window_rounds
    print(f"window rounds={rounds} "
          f"round_s={(rec.window_end - rec.window_start) / rounds!r}",
          file=sys.stderr)
    P.record({"rounds": rounds})        # the in-memory record, on stderr
    print(f"{'trace span':28s} {'calls':>7s} {'host ms/r':>10s} "
          f"{'self ms/r':>10s} {'idle ms/r':>10s}")
    for name, row in sorted(table.items()):
        print(f"{name:28s} {row['calls']:7d} "
              f"{1e3 * row['host_s'] / rounds:10.3f} "
              f"{1e3 * row['self_s'] / rounds:10.3f} "
              f"{1e3 * row['idle_s'] / len(devices) / rounds:10.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
