"""Sweep-level durability: the manifest, cell records, and plan-cache file.

A **durable sweep** (``run_sweep(..., checkpoint_every=R)``) keeps all of
its restartable state under one *state directory*::

    <state_dir>/
      manifest.json            # work-queue ledger (atomic temp+rename)
      plan_cache.json          # PlanCache.state_dict() snapshot
      records/<cell>.json      # finished cells' JSON records
      cells/<cell>/seed<s>/    # RoundCheckpointer round checkpoints

``manifest.json`` is the single source of truth for the work queue: each
cell is ``pending → running → done | failed``.  Every transition is an
atomic :func:`~repro.train.checkpoint.atomic_write_json` rewrite, so a
SIGKILL at any instant leaves a readable manifest.  A cell found ``running``
on resume simply reruns — its round checkpoints make that cheap, and
rerunning from the last boundary is bit-identical to never having died.

Failure isolation: the orchestrator's work queue marks a crashing cell
``failed`` (storing the traceback summary) and moves on; ``failed`` cells
are retried on ``--resume``.  :class:`~repro.fl.resume.Preempted` and
``KeyboardInterrupt`` are ``BaseException``\\ s and deliberately escape this
net — a preemption kills the sweep, as it should.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import time

from repro.core.diffusion import PlanCache
from repro.train.checkpoint import atomic_write_json

__all__ = ["SweepManifest", "cell_slug", "default_state_dir",
           "save_plan_cache_file", "load_plan_cache_file"]

MANIFEST_VERSION = 1

# Config keys that may differ between the original launch and a --resume
# without invalidating stored progress: the checkpoint cadence (resume may
# tighten/loosen it).
_RESUME_SAFE_KEYS = ("checkpoint_every",)


def cell_slug(label: str) -> str:
    """Filesystem-safe name for a cell label (``alpha=0.1/feddif`` →
    ``alpha-0.1__feddif``)."""
    return re.sub(r"[^A-Za-z0-9._-]+", "__",
                  label.replace("/", "__").replace("=", "-"))


def default_state_dir(name: str) -> str:
    """Durable-state home for sweep ``name`` under the artifact dir."""
    from repro.experiments import artifacts
    return os.path.join(artifacts.default_out_dir(), "sweeps", name)


class SweepManifest:
    """The durable work-queue ledger for one sweep run."""

    def __init__(self, state_dir: str, data: dict):
        self.state_dir = state_dir
        self.data = data

    # ------------------------------------------------------------ lifecycle

    @classmethod
    def open(cls, state_dir: str, sweep: str, config: dict,
             labels: list[str], resume: bool) -> "SweepManifest":
        """Create a fresh manifest, or adopt an existing one on resume.

        A fresh (non-resume) open refuses to reuse a state directory that
        already holds a manifest — silently clobbering durable progress is
        exactly the failure mode this module exists to prevent.
        """
        path = cls._path(state_dir)
        if os.path.exists(path):
            if not resume:
                raise FileExistsError(
                    f"{path} already exists — pass resume=True (CLI: "
                    f"--resume) to continue it, or use a fresh state_dir")
            m = cls.load(state_dir)
            m._check_config(config)
            # The grid may legitimately be re-expanded on resume; any label
            # the stored manifest has never seen starts pending.
            for lab in labels:
                m.data["cells"].setdefault(
                    lab, {"status": "pending", "error": None})
            m.data["order"] = list(labels)
            m.data["updated_unix"] = time.time()
            m.flush()
            return m
        if resume and not os.path.isdir(state_dir):
            raise FileNotFoundError(
                f"resume requested but no manifest at {path}")
        data = {
            "version": MANIFEST_VERSION,
            "sweep": sweep,
            "config": _jsonable(config),
            "created_unix": time.time(),
            "updated_unix": time.time(),
            "order": list(labels),
            "cells": {lab: {"status": "pending", "error": None}
                      for lab in labels},
        }
        m = cls(state_dir, data)
        m.flush()
        return m

    @classmethod
    def load(cls, state_dir: str) -> "SweepManifest":
        with open(cls._path(state_dir)) as f:
            return cls(state_dir, json.load(f))

    @staticmethod
    def _path(state_dir: str) -> str:
        return os.path.join(state_dir, "manifest.json")

    @property
    def path(self) -> str:
        return self._path(self.state_dir)

    def flush(self) -> None:
        self.data["updated_unix"] = time.time()
        atomic_write_json(self.path, self.data, indent=2)

    def _check_config(self, config: dict) -> None:
        saved = self.data.get("config", {})
        current = _jsonable(config)
        diffs = {k: (saved.get(k), current.get(k))
                 for k in set(saved) | set(current)
                 if k not in _RESUME_SAFE_KEYS
                 and saved.get(k) != current.get(k)}
        if diffs:
            raise ValueError(
                "refusing to resume: sweep was launched with a different "
                f"configuration — mismatched keys (saved, current): {diffs}")

    # ------------------------------------------------------------ work queue

    def status(self, label: str) -> str:
        return self.data["cells"][label]["status"]

    def mark(self, label: str, status: str, error: str | None = None) -> None:
        cell = self.data["cells"][label]
        cell["status"] = status
        cell["error"] = error
        self.flush()

    def failed_cells(self) -> list[dict]:
        return [{"label": lab, "error": c.get("error")}
                for lab, c in self.data["cells"].items()
                if c["status"] == "failed"]

    # ---------------------------------------------------------- cell records

    def record_path(self, label: str) -> str:
        return os.path.join(self.state_dir, "records",
                            f"{cell_slug(label)}.json")

    def store_record(self, label: str, record: dict) -> None:
        path = self.record_path(label)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        from repro.experiments.artifacts import _json_default
        atomic_write_json(path, record, indent=2, default=_json_default)

    def load_record(self, label: str) -> dict:
        with open(self.record_path(label)) as f:
            return json.load(f)

    # ------------------------------------------------------ cell checkpoints

    def cell_checkpoint_root(self, label: str) -> str:
        return os.path.join(self.state_dir, "cells", cell_slug(label))


# ------------------------------------------------------------- plan cache

def plan_cache_path(state_dir: str) -> str:
    return os.path.join(state_dir, "plan_cache.json")


def save_plan_cache_file(state_dir: str, cache: PlanCache) -> str:
    """Snapshot the sweep-shared plan cache (atomic); resumed runs *replay*
    already-planned control planes instead of replanning them."""
    path = plan_cache_path(state_dir)
    atomic_write_json(path, cache.state_dict())
    return path


def load_plan_cache_file(state_dir: str, cache: PlanCache) -> bool:
    """Merge a saved plan-cache snapshot into ``cache``; False if absent."""
    path = plan_cache_path(state_dir)
    if not os.path.exists(path):
        return False
    with open(path) as f:
        cache.load_state_dict(json.load(f))
    return True


def _jsonable(obj):
    """Round-trip through JSON so stored/loaded configs compare equal
    (tuples become lists, numpy scalars become Python scalars)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        obj = dataclasses.asdict(obj)
    return json.loads(json.dumps(obj, default=str))
