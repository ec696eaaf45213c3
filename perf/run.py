"""The benchmark's one command: one run of one cell.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It reads the cell from ``BENCHMARK.json`` at the root of the checkout and
finds everything else by name: the configuration (``perf/configs/<config>
.json`` with ``<config>_ref.py`` and ``<config>.py`` beside it), the
traffic mix (``perf/traffic/<traffic>.json``), the limits of the check
(``perf/cells/<workload>.json``) and each per-layer metric
(``perf/metrics/<metric>.py``).  It refuses to run off a TPU, or on fewer
chips than the cell asks for, and then prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; its last key, ``check``, repeats each number compared with
its limit, as do the last lines on standard error.
"""
from __future__ import annotations

import time

T_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402

# Run as a script, this directory heads sys.path; its modules must not
# shadow the standard library's.
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402

ROOT = os.path.dirname(HERE)
# A traced run measures its per-layer metrics over the first rounds of
# the window, up to this long: a trace of every operation of a longer one
# costs more to write and read than a run may take.
TRACE_SECONDS = 10.0


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"perf: no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` (end_to_end / per_layer) this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def limits_of(cell: str) -> dict:
    with open(os.path.join(ROOT, "perf", "cells", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed place in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerator(chips: int):
    """The devices of the run, or None off a TPU or short of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"perf: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"perf: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs[:chips]


def run(args, bench: dict, cell: dict, devices, require_tpu: bool = True,
        fault: str | None = None, mix: dict | None = None,
        conf: dict | None = None) -> dict:
    """Everything after the look for a chip: set-up, window, metrics and
    the check.  Returns the result object."""
    from perf import harness as H
    from perf import devtrace as T
    from perf.peaks import peaks_for
    from perf.traffic.generate import load_mix

    conf_file, ref, glue = H.load_config(cell["config"])
    conf = conf or conf_file
    mix = mix or load_mix(cell["traffic"])
    limits = limits_of(cell["name"])
    traced = bool(args.trace)
    with H.trace_dir_for(traced) as tdir:
        seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
        rec, traffic, spans, seeds = H.drive(
            conf, ref, glue, mix, args.seed, seconds, tdir,
            fault=fault)
        memory = H.memory_peak(devices)
        window_s = rec.window_end - rec.window_start
        rounds = rec.window_rounds
        stamps = [rec.window_start] + rec.stamps[-rounds:]
        per_round = [b - a for a, b in zip(stamps, stamps[1:])]
        print(f"window rounds_s={per_round!r}", file=sys.stderr)
        kind = devices[0].device_kind
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices), "memory_peak_bytes": memory}
        ctx = {"cell": cell, "conf": conf, "mix": mix, "rounds": rounds,
               "window_s": window_s, "per_round_s": per_round,
               "spans": spans, "rows": rec.rows_window,
               "compiles": rec.window_compiles, "memory_peak_bytes": memory,
               "devices": len(devices), "ref": ref,
               "peaks": peaks_for(kind) if require_tpu else None}
        breakdown = None
        if traced:
            reduced = T.reduce_dir(tdir, len(devices))
            ctx["trace"] = reduced
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = reduced.breakdown()
    metrics = {}
    if traced:
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            mod = importlib.import_module(f"perf.metrics.{m['name']}")
            value = mod.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        setup_s = rec.window_start - (T_START - time.time()
                                      + time.perf_counter())
        e2e = {"setup_s": setup_s, "round_s": window_s / rounds}
        if len(per_round) >= 10:
            e2e["round_s_p90"] = statistics.quantiles(per_round, n=10)[-1]
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    # A number the cell's file gives no limit is not compared (PERF.md says
    # which, and why).
    numbers = H.check(conf, ref, glue, mix, rec, traffic, seeds)
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": rounds, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    bench = manifest()
    cell = find_cell(bench, args.workload)
    enable_compile_cache()
    devices = accelerator(int(cell["chips"]))
    if devices is None:
        return 2
    out = run(args, bench, cell, devices)
    for k, c in out["check"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
