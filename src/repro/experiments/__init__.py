"""Paper-figure sweep orchestration.

Public API:

* :mod:`repro.experiments.registry` — the declarative sweep registry
  (``REGISTRY``, :class:`SweepDef`, :class:`SweepCell`,
  :func:`expand_sweep`): one entry per paper figure/table.
* :mod:`repro.experiments.orchestrator` — :func:`run_sweep` /
  :func:`run_cell`: expand a registry entry, run it with multi-seed
  replication and a shared diffusion-plan cache, emit a
  ``BENCH_feddif_<sweep>.json`` artifact.
* :mod:`repro.experiments.replicate` — multi-seed replication
  (:func:`run_replicates_loop`).
* :mod:`repro.experiments.artifacts` — artifact schema and writer.
* :mod:`repro.experiments.durability` — durable sweeps: the work-queue
  manifest, per-cell records and plan-cache snapshot behind
  ``run_sweep(..., checkpoint_every=R)`` / ``resume=True``.

CLI: ``PYTHONPATH=src python -m repro.launch.sweep --sweep fig3_alpha --smoke``.
"""
from repro.experiments.artifacts import (bench_file, bench_path,
                                         build_artifact, default_out_dir,
                                         strip_volatile, write_artifact,
                                         write_bench_json)
from repro.experiments.durability import (SweepManifest, cell_slug,
                                          default_state_dir)
from repro.experiments.orchestrator import run_cell, run_sweep
from repro.experiments.registry import (REGISTRY, SweepCell, SweepDef,
                                        expand_sweep, get_sweep, register,
                                        sweep_names)
from repro.experiments.replicate import run_replicates_loop

__all__ = [
    "REGISTRY", "SweepCell", "SweepDef", "expand_sweep", "get_sweep",
    "register", "sweep_names",
    "run_cell", "run_sweep",
    "run_replicates_loop",
    "bench_file", "bench_path", "build_artifact", "default_out_dir",
    "strip_volatile", "write_artifact", "write_bench_json",
    "SweepManifest", "cell_slug", "default_state_dir",
]
