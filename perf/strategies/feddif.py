"""FedDif's round (Alg. 1): every holder trains its model, then up to
``max_diffusion_rounds`` D2D hops each move models between clients, each
receiver training what it got; the server weights each model by the data
of the chain that trained it (Eq. 11).

Each hop is re-derived from the slot permutation and train mask: a model
trains on a client at most once, and the link it hops over is feasible
(spectral efficiency >= gamma_min) on the reference's own channel draw."""
from __future__ import annotations

import numpy as np

from perf import reference as R


def d2d_rounds(mix: dict) -> int:
    return int(mix["max_diffusion_rounds"])


def plan(sched, mix: dict, sizes: np.ndarray, up: np.ndarray, d2d: list,
         bits: float, ledger: R.Ledger) -> R.RoundPlan:
    n, m = int(mix["clients"]), int(mix["models"])
    if m != n:
        raise ValueError("the reference follows fleets with M == N")
    faults: list = []
    expect_d2d: list = []
    ops = list(sched.ops)
    kinds = [type(op).__name__ for op in ops]
    slot_model = np.arange(n)               # model held by each slot
    holder = np.arange(n)                   # client that trained it last
    visited = np.eye(n, dtype=bool)
    chain = sizes.astype(np.float64).copy()
    if not kinds or kinds[0] != "TrainOp" or not np.all(ops[0].train_mask):
        faults.append("first op is not the holders' training")
    sessions = [(None, np.ones(n, bool))]
    if len(ops) - 1 > int(mix["max_diffusion_rounds"]):
        faults.append(f"{len(ops) - 1} diffusion rounds")
    for k, op in enumerate(ops[1:]):
        if type(op).__name__ != "PermuteOp" or op.compress:
            faults.append(f"op {k + 1} is {type(op).__name__}")
            continue
        perm = np.asarray(op.src_of_dst, np.int64)
        mask = np.asarray(op.train_mask, bool)
        if sorted(perm.tolist()) != list(range(n)):
            faults.append(f"round {k}: not a slot permutation")
            continue
        slot_model = slot_model[perm]
        for d in np.flatnonzero(mask):
            mi = int(slot_model[d])
            src = int(holder[mi])
            g = float(d2d[k][src, d]) if k < len(d2d) else 0.0
            if visited[mi, d]:
                faults.append(f"round {k}: model {mi} retrains on {d}")
            # the planner compares in float32
            if g < float(mix["gamma_min"]) * (1 - 1e-6):
                faults.append(f"round {k}: infeasible hop {src}->{d}")
            visited[mi, d] = True
            holder[mi] = d
            chain[mi] += sizes[d]
            expect_d2d.append((src, max(g, R.GAMMA_FLOOR)))
            ledger.d2d(bits, max(g, R.GAMMA_FLOOR))
        sessions.append((perm, mask))
    weights = np.zeros(n, np.float64)
    weights[np.argsort(slot_model)] = chain
    for mi in range(m):
        ledger.uplink(bits, float(up[holder[mi]]))
    got = sorted((int(e.src), round(float(e.gamma), 9))
                 for e in sched.wire if e.kind == "d2d")
    want = sorted((s, round(g, 9)) for s, g in expect_d2d)
    if got != want:
        faults.append("d2d wire events differ from the hops")
    return R.RoundPlan(sessions=sessions, weights=weights, faults=faults)
