"""The readings a cell's limits are set from (steps 3-5 of the check).

    python3 perf/readings.py --workload <name> --seeds 12 --base <seed>
        [--control 3] [--faults 3] [--out <file>]

For each seed it runs the program for the three rounds the reference
follows (no measured window) and prints the numbers the run compares:
``sound``.  On the first ``--control`` seeds it also reads the control,
the reference in the next precision below the configuration's put in the
program's place (``CONTROL`` of ``perf/configs/<config>.py``).  On the
first ``--faults`` seeds it plants each fault the cell can have in the
program's timed path and reads it: half of every batch left out, and
(FedDif) the hop between clients left out.  A step that returns its state
unchanged reads 1 on the change numbers by their definition and needs no
run.  Off a TPU it refuses to run, as the benchmark does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
ROOT = os.path.dirname(HERE)


def read_seed(cell: dict, seed: int, control: bool, faults: bool) -> dict:
    import jax.numpy as jnp

    from perf import harness as H
    from perf import reference as R
    from perf.traffic.generate import load_mix

    conf, ref, glue = H.load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    rec, traffic, _, seeds = H.drive(conf, ref, glue, mix,
                                     seed, 0.0, None,
                                     stop_after=H.WARMUP_ROUNDS)
    frozen = rec.frozen
    params0, ref_g, grad1, found, ledger = H.reference_globals(
        conf, ref, glue, mix, traffic, seeds, rec.schedules, frozen=frozen)
    ref_reading = R.reading_of(ref, conf, traffic, params0, ref_g, grad1,
                               glue.EVAL_BLOCK, frozen=frozen)
    out = {"seed": seed}
    out["sound"] = R.compare(H.program_reading(rec, params0), ref_reading)
    out["sound"]["ledger_gap"] = ledger.gap(rec.ledger)
    out["sound"]["schedule_faults"] = len(found)
    if control:
        ctl = glue.CONTROL
        _, ctl_g, ctl_grad1, _, _ = H.reference_globals(
            conf, ref, glue, mix, traffic, seeds, rec.schedules,
            compute_dtype=ctl["compute_dtype"],
            param_dtype=ctl["param_dtype"], frozen=frozen)
        out["control"] = R.compare(
            R.reading_of(ref, conf, traffic, params0, ctl_g, ctl_grad1,
                         glue.EVAL_BLOCK, jnp.dtype(ctl["compute_dtype"]),
                         frozen),
            ref_reading)
    if faults:
        kinds = ["half_batch"] + (["no_hop"] if mix["strategy"] == "feddif"
                                  else [])
        for kind in kinds:
            r, _, _, _ = H.drive(conf, ref, glue, mix, seed,
                                 0.0, None, fault=kind,
                                 stop_after=H.WARMUP_ROUNDS)
            out[kind] = R.compare(H.program_reading(r, params0), ref_reading)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base", type=int, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from perf import run as RUN
    cell = RUN.find_cell(RUN.manifest(), args.workload)
    RUN.enable_compile_cache()
    if RUN.accelerator(int(cell["chips"])) is None:
        return 2
    sink = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        t0 = time.time()
        line = read_seed(cell, args.base + 7919 * i, i < args.control,
                         i < args.faults)
        line["seconds"] = time.time() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
