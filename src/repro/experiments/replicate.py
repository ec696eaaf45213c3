"""Multi-seed replication.

:func:`run_replicates_loop` runs one sweep cell at ``S`` replicate seeds:
one :func:`~repro.fl.experiment.run_experiment` call per seed (any
strategy, any engine), sharing the plan cache so FedDif's control plane is
replayed, not replanned, for seeds after the first.  It returns one
:class:`~repro.fl.server.FLResult` per seed; their ledgers are identical
(communication is seed-independent given ``FLConfig.topology_seed``).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Sequence

from repro.core.diffusion import PlanCache
from repro.fl.experiment import ExperimentSpec, run_experiment
from repro.fl.server import FLResult

__all__ = ["run_replicates_loop"]


def run_replicates_loop(spec: ExperimentSpec, seeds: Sequence[int],
                        plan_cache: PlanCache | None = None,
                        checkpoint_root: str | None = None
                        ) -> list[FLResult]:
    """One ``run_experiment`` per seed; plan cache shared across seeds.

    ``checkpoint_root`` (durable sweeps) gives each replicate seed its own
    round-checkpoint directory ``<root>/seed<seed>`` — a preempted cell
    resumes mid-cohort: finished seeds rerun from their final checkpoint in
    O(1 rounds), the interrupted seed from its last boundary.
    """
    results = []
    for s in seeds:
        spec_s = dataclasses.replace(
            spec, fl=dataclasses.replace(spec.fl, seed=int(s)))
        ckpt_dir = (os.path.join(checkpoint_root, f"seed{int(s)}")
                    if checkpoint_root is not None else None)
        results.append(run_experiment(spec_s, plan_cache=plan_cache,
                                      checkpoint_dir=ckpt_dir))
    return results
