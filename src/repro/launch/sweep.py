"""Paper-figure sweep CLI — reproduce a figure/table with one command.

    PYTHONPATH=src python -m repro.launch.sweep --sweep fig3_alpha --smoke
    PYTHONPATH=src python -m repro.launch.sweep --sweep all --full --seeds 3
    PYTHONPATH=src python -m repro.launch.sweep --list

Durable mode (kill-safe, bit-identical resume)::

    python -m repro.launch.sweep --sweep fig3_alpha --checkpoint-every 1
    # ... SIGTERM / crash / power loss ...
    python -m repro.launch.sweep --sweep fig3_alpha --resume

Expands the named entry of the sweep registry
(:mod:`repro.experiments.registry`), runs every cell with multi-seed
replication (one run per seed on the cell's engine; diffusion plans cached
across seeds), and writes a
``BENCH_feddif_<sweep>.json`` artifact with per-cell accuracy curves, the
Eq.-15 cumulative PUSCH bandwidth, sub-frame counts and wall-clock.
Artifacts land in the repo-wide BENCH directory
(``$REPRO_BENCH_DIR`` or ``benchmarks/results/`` — see
``repro.experiments.artifacts.default_out_dir``) unless ``--out-dir`` says
otherwise.  ``benchmarks/run.py`` drives the same registry — definitions
live in one place.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

from repro.experiments import REGISTRY, run_sweep, sweep_names
from repro.experiments.artifacts import default_out_dir
from repro.fl.engine import ENGINE_PRESETS, EngineSpec
from repro.launch.compile_cache import enable_compile_cache

__all__ = ["main"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.sweep",
        description="Run a registered paper-figure sweep and write "
                    "BENCH_feddif_<sweep>.json")
    ap.add_argument("--sweep", default=None,
                    help="registry name (see --list) or 'all'")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-sized grid (default unless --full)")
    ap.add_argument("--full", action="store_true",
                    help="paper-approaching grid sizes")
    ap.add_argument("--seeds", type=int, default=1,
                    help="number of replicate seeds (0..N-1)")
    ap.add_argument("--engine", choices=sorted(ENGINE_PRESETS),
                    default="host",
                    help="EngineSpec preset stamped on every cell: host "
                         "reference loop, client-stacked fleet, "
                         "client-sharded mesh (downgraded to fleet below "
                         "the crossover N), auto, or the buffered-async "
                         "round plane (async, and async_barrier for its "
                         "sync comparison arm)")
    ap.add_argument("--planner", choices=["host", "jax"], default="host",
                    help="control plane per cell: host numpy oracle or "
                         "batched jax device planner that pre-plans the "
                         "whole sweep in one device call (EngineSpec.planner)")
    ap.add_argument("--out-dir", default=None,
                    help="artifact directory (default: "
                         "$REPRO_BENCH_DIR or benchmarks/results/ — the "
                         "same place benchmarks/run.py writes)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    metavar="R",
                    help="durable mode: checkpoint full round state every "
                         "R communication rounds; a killed sweep restarts "
                         "bit-identically with --resume")
    ap.add_argument("--resume", action="store_true",
                    help="continue a previous durable run from its "
                         "manifest (done cells load stored records, "
                         "interrupted cells restart from their latest "
                         "round checkpoint, failed cells are retried)")
    ap.add_argument("--state-dir", default=None,
                    help="durable-state directory (default: "
                         "<artifact dir>/sweeps/<sweep>; with --sweep all, "
                         "a per-sweep subdirectory of this path)")
    ap.add_argument("--num-samples", type=int, default=None,
                    help="override ExperimentSpec.num_samples per cell "
                         "(small values make smoke/CI runs fast)")
    ap.add_argument("--list", action="store_true",
                    help="list registered sweeps and exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.list or not args.sweep:
        print(f"{'name':20s} {'paper':16s} axis        description")
        for name in sweep_names():
            d = REGISTRY[name]
            print(f"{name:20s} {d.figure:16s} {d.axis:11s} {d.description}")
        return 0

    smoke = not args.full
    if args.seeds < 1:
        print("error: --seeds must be >= 1", file=sys.stderr)
        return 2
    if args.sweep != "all" and args.sweep not in REGISTRY:
        print(f"error: unknown sweep {args.sweep!r}; registered: "
              f"{', '.join(sweep_names())} (or 'all')", file=sys.stderr)
        return 2
    names = sweep_names() if args.sweep == "all" else [args.sweep]
    seeds = tuple(range(args.seeds))
    out_dir = args.out_dir if args.out_dir is not None else default_out_dir()
    engine = dataclasses.replace(EngineSpec.preset(args.engine),
                                 planner=args.planner)
    overrides = {}
    if args.num_samples is not None:
        overrides["num_samples"] = args.num_samples
    durable = (args.checkpoint_every > 0 or args.resume
               or args.state_dir is not None)
    n_failed = 0
    for name in names:
        print(f"# === sweep {name} ({'smoke' if smoke else 'full'}, "
              f"seeds={list(seeds)}) ===", flush=True)
        state_dir = args.state_dir
        if state_dir is not None and args.sweep == "all":
            state_dir = os.path.join(state_dir, name)
        artifact = run_sweep(name, smoke=smoke, seeds=seeds,
                             out_dir=out_dir, engine=engine,
                             checkpoint_every=args.checkpoint_every,
                             resume=args.resume,
                             state_dir=state_dir if durable else None,
                             log=lambda s: print(s, flush=True),
                             **overrides)
        pc = artifact["plan_cache"]
        failed = artifact.get("failed_cells", [])
        print(f"# wrote {artifact['path']} "
              f"(cells={len(artifact['cells'])}, "
              f"failed={len(failed)}, "
              f"plan_cache hits={pc.get('hits', 0)} "
              f"misses={pc.get('misses', 0)}, "
              f"{artifact['wall_clock_s']:.1f}s)", flush=True)
        if "manifest" in artifact:
            print(f"# manifest {artifact['manifest']}", flush=True)
        for fc in failed:
            print(f"# FAILED cell {fc['label']}: {fc['error']}",
                  file=sys.stderr, flush=True)
        n_failed += len(failed)
    # Each failed cell is isolated so the sweep finishes, but the run as a
    # whole did not succeed.
    return 1 if n_failed else 0


if __name__ == "__main__":
    sys.exit(main())
