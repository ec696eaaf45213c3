"""Durable round-state checkpointing for ``run_federated`` — the resume seam.

Production FL fleets get preempted; the 6G FL surveys (arXiv 2111.07392,
2310.05269) treat client/server failure and partial progress as the defining
deployment constraints.  :class:`RoundCheckpointer` makes one experiment cell
preemption-proof: every ``FLConfig.checkpoint_every`` communication rounds it
serializes the **full** round state at the round boundary,

* the global model parameters (and, for persistent strategies like gossip /
  tthf, the per-slot state via the executor's ``capture_slots`` /
  ``adopt_slots`` hooks — each executor restores onto its own placement),
* the cumulative Eq.-15 :class:`~repro.channels.resources.ResourceLedger`,
* the accuracy / loss / diffusion-round / IID-distance histories,
* every RNG stream position: the model-seed generator's bit-generator state
  and the per-client data-shuffle cursors
  (:attr:`~repro.data.pipeline.ClientLoader.epochs_drawn`).  The control
  plane (positions / channel / plan draws) and churn streams are keyed
  ``[seed, t, tag]`` per round, so restarting the loop at round ``t``
  reproduces them exactly with no stored position,

through :mod:`repro.train.checkpoint` (atomic npz + metadata-JSON commit
marker).  A run resumed from any boundary is **bit-identical** to an
uninterrupted one: same params, same ledger, same curves — the property the
``tests/test_resume_orchestration.py`` fault-injection harness asserts for
all three executors.

:class:`Preempted` is the harness's in-process kill switch: a
``BaseException`` (like ``KeyboardInterrupt``) so the sweep orchestrator's
per-cell failure isolation — which catches ``Exception`` only — never
swallows a simulated (or real) preemption.
"""
from __future__ import annotations

import os
from typing import Any, Callable

import jax
import numpy as np

from repro.channels.resources import ResourceLedger
from repro.train.checkpoint import (load_metadata, restore_checkpoint,
                                    save_checkpoint, valid_steps)

__all__ = ["RoundCheckpointer", "Preempted", "RoundState"]

# FLConfig fields a checkpoint must agree on to be restorable: anything that
# alters the trajectory.  The cadence (checkpoint_every) is deliberately
# absent — changing it on resume is safe.  The resolved EngineSpec is
# guarded separately (``engine_fingerprint``): async-buffer state is only
# meaningful under the engine knobs that produced it.
_CONFIG_GUARD = ("strategy", "num_clients", "num_models", "rounds",
                 "local_epochs", "lr", "momentum", "batch_size", "epsilon",
                 "gamma_min", "metric", "stc_sparsity", "prox_mu", "seed",
                 "topology_seed", "churn_rate", "allow_retraining",
                 "underlay")


class Preempted(BaseException):
    """Simulated preemption raised at a round boundary (fault injection).

    Deliberately not an ``Exception``: cell-level failure isolation in the
    sweep work-queue must let preemptions propagate and kill the sweep, the
    same way SIGTERM would.
    """


class RoundState:
    """What a resumed ``run_federated`` gets back (plain attribute bag)."""

    def __init__(self, step: int, params: Any, slots: Any,
                 ledger: ResourceLedger, meta: dict,
                 buffer_tree: Any = None):
        self.step = step
        self.params = params
        self.slots = slots
        self.ledger = ledger
        self.acc_hist = [float(x) for x in meta["acc_hist"]]
        self.loss_hist = [float(x) for x in meta["loss_hist"]]
        self.dif_hist = [int(x) for x in meta["dif_hist"]]
        self.iid_hist = [float(x) for x in meta["iid_hist"]]
        self.round_wall = [float(x) for x in meta["round_wall"]]
        self.rng_state = meta["rng_state"]
        self.extra = meta.get("extra")
        # Async round plane: extra history curves and the mid-tick pending
        # buffer (stacked contribution pytree + JSON-able entry metadata).
        self.async_hist = meta.get("async_hist")
        self.buffer_meta = meta.get("buffer") or {"count": 0,
                                                  "virtual_s": 0.0,
                                                  "next_seq": 0}
        self.buffer_tree = buffer_tree


class RoundCheckpointer:
    """Serialize/restore ``run_federated`` round state every R rounds.

    Args:
      directory: per-cell-per-seed checkpoint directory.
      every: cadence R in communication rounds (>=1).
      capture_extra / restore_extra: caller-owned data-plane cursors — the
        experiment harness passes the per-client loader shuffle positions
        here, keeping ``run_federated`` agnostic of where batches come from.
      keep: how many round checkpoints to retain (older ones are pruned
        after a successful save; >=2 so a corrupt latest can fall back).
      fail_after_save: fault injection for the kill/resume test harness —
        after the checkpoint for this step is durably on disk, raise
        :class:`Preempted`.  Also a *class* attribute (default ``None``) so
        the fault-injection tests can arm every checkpointer a sweep
        constructs with one monkeypatch.
    """

    fail_after_save: int | None = None

    def __init__(self, directory: str, every: int = 1,
                 capture_extra: Callable[[], Any] | None = None,
                 restore_extra: Callable[[Any], None] | None = None,
                 keep: int = 2, fail_after_save: int | None = None):
        self.directory = directory
        self.every = max(1, int(every))
        self.capture_extra = capture_extra
        self.restore_extra = restore_extra
        self.keep = max(2, int(keep))
        if fail_after_save is not None:
            self.fail_after_save = fail_after_save

    # ------------------------------------------------------------- cadence

    def due(self, step: int, total_rounds: int) -> bool:
        """Save at round boundary ``step`` (= rounds completed)?  The final
        round never checkpoints — the finished result supersedes it."""
        return step < total_rounds and step % self.every == 0

    # ---------------------------------------------------------------- save

    def save(self, step: int, executor, params: Any, slots: Any,
             ledger: ResourceLedger, cfg, *, acc_hist, loss_hist, dif_hist,
             iid_hist, round_wall, rng: np.random.Generator,
             async_hist: dict | None = None, buffer_tree: Any = None,
             buffer_meta: dict | None = None) -> str:
        """Serialize one round boundary.

        ``async_hist`` / ``buffer_tree`` / ``buffer_meta`` are the buffered-
        async engine's additions: the virtual-clock curves and the pending
        contribution buffer (a stacked leading-axis pytree plus per-entry
        arrival/round/slot/weight metadata).  The buffer rides the same
        atomic npz + commit-marker protocol as params, so a kill between
        server ticks resumes with the exact mid-tick buffer state.
        """
        tree = {"params": jax.device_get(params)}
        saved_slots = executor.capture_slots(slots)
        if saved_slots is not None:
            tree["slots"] = saved_slots
        if buffer_tree is not None:
            tree["abuf"] = jax.device_get(buffer_tree)
        meta = {
            "config": {k: getattr(cfg, k) for k in _CONFIG_GUARD},
            "engine": _engine_fingerprint(cfg),
            "ledger": ledger.as_dict(),
            "acc_hist": [float(x) for x in acc_hist],
            "loss_hist": [float(x) for x in loss_hist],
            "dif_hist": [int(x) for x in dif_hist],
            "iid_hist": [float(x) for x in iid_hist],
            "round_wall": [float(x) for x in round_wall],
            "rng_state": _rng_state_jsonable(rng),
            "num_slots": (None if saved_slots is None
                          else executor.num_slots_of(saved_slots)),
            "has_slots": saved_slots is not None,
            "extra": (self.capture_extra()
                      if self.capture_extra is not None else None),
        }
        if async_hist is not None:
            meta["async_hist"] = {k: list(v) for k, v in async_hist.items()}
        if buffer_meta is not None:
            meta["buffer"] = buffer_meta
        path = save_checkpoint(self.directory, step, tree, metadata=meta)
        self._prune(step)
        if self.fail_after_save is not None and step == self.fail_after_save:
            raise Preempted(f"simulated preemption after round-{step} "
                            f"checkpoint in {self.directory!r}")
        return path

    def _prune(self, newest: int) -> None:
        steps = valid_steps(self.directory)
        for s in steps[:-self.keep]:
            for suffix in (".npz", ".json"):
                p = os.path.join(self.directory, f"ckpt_{s:08d}{suffix}")
                if os.path.exists(p):
                    os.remove(p)

    # ------------------------------------------------------------- restore

    def restore(self, executor, params_template: Any, cfg
                ) -> RoundState | None:
        """Latest readable round state, or ``None`` (fresh start).

        Walks checkpoints newest-first, skipping unreadable ones with a
        warning (see :func:`repro.train.checkpoint.restore_latest` for the
        fallback contract).  Raises ``ValueError`` if a readable checkpoint
        was written by an incompatible ``FLConfig``.
        """
        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        import warnings
        for step in reversed(valid_steps(self.directory)):
            try:
                meta = load_metadata(self.directory, step)
            except Exception as e:                  # noqa: BLE001
                warnings.warn(
                    f"round checkpoint {step} metadata unreadable "
                    f"({type(e).__name__}: {e}); falling back",
                    RuntimeWarning, stacklevel=2)
                continue
            self._guard_config(meta, cfg)
            like = {"params": jax.tree.map(sds, params_template)}
            if meta["has_slots"]:
                like["slots"] = executor.slots_like(params_template,
                                                    int(meta["num_slots"]))
            nbuf = int((meta.get("buffer") or {}).get("count", 0))
            if nbuf > 0:
                # Pending async contributions: params-shaped trees stacked
                # on a leading entry axis.
                like["abuf"] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct((nbuf,) + x.shape,
                                                   x.dtype),
                    params_template)
            try:
                tree = restore_checkpoint(self.directory, step, like)
            except Exception as e:                  # noqa: BLE001
                warnings.warn(
                    f"round checkpoint {step} arrays unreadable "
                    f"({type(e).__name__}: {e}); falling back",
                    RuntimeWarning, stacklevel=2)
                continue
            slots = (executor.adopt_slots(tree["slots"])
                     if meta["has_slots"] else None)
            ledger = ResourceLedger(**meta["ledger"])
            state = RoundState(step, tree["params"], slots, ledger, meta,
                               buffer_tree=tree.get("abuf"))
            if self.restore_extra is not None and state.extra is not None:
                self.restore_extra(state.extra)
            return state
        return None

    @staticmethod
    def _guard_config(meta: dict, cfg) -> None:
        saved = meta.get("config", {})
        diffs = {k: (saved.get(k), getattr(cfg, k)) for k in _CONFIG_GUARD
                 if k in saved and saved[k] != getattr(cfg, k)}
        if "engine" in meta and meta["engine"] != _engine_fingerprint(cfg):
            diffs["engine"] = (meta["engine"], _engine_fingerprint(cfg))
        if diffs:
            raise ValueError(
                "refusing to resume: checkpoint was written by a different "
                f"config — mismatched fields (saved, current): {diffs}")

    @staticmethod
    def apply_rng_state(rng: np.random.Generator, state: dict) -> None:
        """Reposition the model-seed generator to its checkpointed state."""
        rng.bit_generator.state = _rng_state_from_jsonable(state)


def _engine_fingerprint(cfg) -> str:
    from repro.fl.engine import engine_fingerprint
    return engine_fingerprint(cfg)


def _rng_state_jsonable(rng: np.random.Generator) -> dict:
    # bit_generator.state is a nested dict of ints/str; numpy keeps the
    # 128-bit PCG64 state as Python ints, which JSON carries exactly.
    return rng.bit_generator.state


def _rng_state_from_jsonable(state: dict) -> dict:
    return state
