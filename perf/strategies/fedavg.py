"""FedAvg's round: every client trains the broadcast model once, uploads
it, and the server takes the data-size weighted mean (Eq. 11)."""
from __future__ import annotations

import numpy as np

from perf import reference as R


def d2d_rounds(mix: dict) -> int:
    return 0


def plan(sched, mix: dict, sizes: np.ndarray, up: np.ndarray, d2d: list,
         bits: float, ledger: R.Ledger) -> R.RoundPlan:
    n = int(mix["clients"])
    ops = list(sched.ops)
    kinds = [type(op).__name__ for op in ops]
    faults = []
    if kinds != ["TrainOp"] or not np.all(ops[0].train_mask):
        faults.append(f"fedavg ops {kinds}")
    for i in range(n):
        ledger.uplink(bits, float(up[i]))
    return R.RoundPlan(sessions=[(None, np.ones(n, bool))],
                       weights=sizes.astype(np.float64).copy(),
                       faults=faults)
