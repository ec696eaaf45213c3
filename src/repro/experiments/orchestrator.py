"""Sweep orchestrator: registry entry -> grid -> replicated runs -> artifact.

``run_sweep`` is the one-command reproduction path for a paper figure::

    from repro.experiments import run_sweep
    artifact = run_sweep("fig3_alpha", smoke=True, seeds=(0, 1, 2))

For every cell of the sweep's grid it

1. stamps a shared ``topology_seed`` so the wireless control plane is
   independent of the replicate seed,
2. runs the cell at every seed, one ``run_federated`` per seed on the
   cell's engine (:func:`~repro.experiments.replicate.run_replicates_loop`),
3. shares one :class:`~repro.core.diffusion.PlanCache` across the whole
   sweep, so FedDif's host-side auction loop runs once per distinct
   (topology seed, round, partition, ε, γ_min) and is *replayed* for every
   other replicate, and
4. folds the per-seed accuracy/loss curves, Eq.-15 cumulative PUSCH
   bandwidth, sub-frame ledger and wall-clock into one JSON cell record.

The CLI wrapper lives in ``repro.launch.sweep``; ``benchmarks/run.py``
drives the same function, so sweep definitions exist in exactly one place
(:mod:`repro.experiments.registry`).
"""
from __future__ import annotations

import os
import time
from typing import Sequence

import numpy as np

from repro.channels.fading import ChannelModel
from repro.channels.topology import CellTopology
from repro.core.auction import AuctionConfig
from repro.core.diffusion import PlanCache, feddif_cache_key
from repro.core.dol import DiffusionState
from repro.experiments import artifacts
from repro.experiments.registry import SweepCell, expand_sweep, get_sweep
from repro.experiments.replicate import run_replicates_loop
from repro.fl.engine import SHARDED_CROSSOVER_N, EngineSpec, resolve_engine
from repro.fl.server import _uplink_gamma

__all__ = ["run_cell", "run_sweep", "prepopulate_plan_cache"]

_FEDDIF_STRATEGIES = ("feddif", "feddif_stc", "feddif_prox")


def prepopulate_plan_cache(cells: Sequence[SweepCell], cache: PlanCache
                           ) -> dict:
    """Plan every FedDif cell × communication round in batched device calls.

    For each eligible cell (FedDif family, an engine whose planner is
    ``"jax"``, topology seed set, no underlay) this replays the
    control-plane RNG exactly as ``run_federated`` would (positions →
    uplink draw → Rayleigh rounds),
    builds one :class:`~repro.core.planner.PlanInputs` per communication
    round, groups them by static signature (N, M, C, max_rounds, metric,
    retraining) and plans each group in **one** vmapped device call.  The
    decoded plans + post-plan states land in ``cache`` under the same
    :func:`~repro.core.diffusion.feddif_cache_key` the schedulers build, so
    every subsequent ``run_cell`` — any replicate seed — replays
    instead of replanning.

    Returns ``{"planned": k, "skipped": j, "batches": b}``.
    """
    from repro.core.diffusion import DiffusionPlanner
    from repro.core.planner import (decode_plan, plan_round_inputs,
                                    plan_rounds_batched)
    from repro.fl.experiment import load_experiment_data, spec_model_bits

    groups: dict[tuple, list] = {}
    skipped = 0
    for cell in cells:
        cfg = cell.spec.fl
        if (cell.strategy not in _FEDDIF_STRATEGIES
                or resolve_engine(cfg).planner != "jax"
                or cfg.topology_seed is None or cfg.underlay
                or getattr(cfg, "scenario", "static") != "static"
                or getattr(cfg, "uncertainty_weight", 0.0) > 0.0):
            # Non-static worlds replay their own RNG/mobility inside
            # run_federated; value-fused plans depend on each seed's params.
            skipped += 1
            continue
        _, _, part, _ = load_experiment_data(cell.spec, with_loaders=False)
        dsi, data_sizes = part.dsi, part.data_sizes
        n, m, c = cfg.num_clients, cfg.num_models, dsi.shape[1]
        model_bits = spec_model_bits(cell.spec)
        topology = CellTopology(num_pues=n)
        channel = ChannelModel()
        auction = AuctionConfig(gamma_min=cfg.gamma_min, metric=cfg.metric,
                                allow_retraining=cfg.allow_retraining,
                                model_bits=model_bits)
        planner = DiffusionPlanner(topology, channel, auction,
                                   epsilon=cfg.epsilon,
                                   max_rounds=cfg.max_diffusion_rounds,
                                   mode="jax")
        max_rounds = cfg.max_diffusion_rounds or n * (n - 1)
        for t in range(cfg.rounds):
            key = feddif_cache_key(cfg, t, dsi, data_sizes, model_bits,
                                   auction)
            if key in cache:
                skipped += 1
                continue
            # Mirror run_federated's control-plane stream for round t.
            ctrl_rng = np.random.default_rng([cfg.topology_seed, t])
            pos = topology.sample_positions(ctrl_rng, n)
            _uplink_gamma(channel, pos, ctrl_rng)     # keep stream aligned
            state = DiffusionState.init(m, n, c)
            for mi in range(m):
                holder = int(state.holder[mi])
                state.record_training(mi, holder, dsi[holder],
                                      float(data_sizes[holder]))
            inp, gamma64 = plan_round_inputs(planner, state, dsi, data_sizes,
                                             ctrl_rng, positions=pos)
            sig = (n, m, c, max_rounds, cfg.metric, cfg.allow_retraining)
            groups.setdefault(sig, []).append(
                (key, inp, state, m, gamma64, model_bits))

    planned = 0
    for sig, items in groups.items():
        _, _, _, max_rounds, metric, allow_retraining = sig
        outs = plan_rounds_batched([inp for _, inp, _, _, _, _ in items],
                                   metric=metric,
                                   allow_retraining=allow_retraining)
        for (key, _, state, m, gamma64, model_bits), out in zip(items, outs):
            if not bool(out.converged):
                import warnings
                warnings.warn("sweep pre-planner: an auction hit its "
                              "iteration cap; the cached plan may be "
                              "truncated", RuntimeWarning, stacklevel=2)
            plan = decode_plan(out, num_models=m, gamma_seq64=gamma64,
                               model_bits=model_bits)
            state.update_from(out.state, rounds_advanced=int(out.num_rounds))
            cache.store(key, plan, state)
            planned += 1
    return {"planned": planned, "skipped": skipped, "batches": len(groups)}


def _pick_executor(cell: SweepCell) -> SweepCell:
    """Downgrade a sharded cell below :data:`SHARDED_CROSSOVER_N` to the
    fleet plane (:meth:`EngineSpec.auto`), stamping the picked spec back
    onto the cell."""
    cfg = cell.spec.fl
    spec = resolve_engine(cfg)
    picked = spec.auto(cfg.num_clients)
    if picked is spec:
        return cell
    print(f"orchestrator,{cell.label},executor={picked.mode},"
          f"reason=N={cfg.num_clients}<crossover={SHARDED_CROSSOVER_N}",
          flush=True)
    return cell.with_fl(engine=picked)


def run_cell(cell: SweepCell, seeds: Sequence[int],
             plan_cache: PlanCache | None = None,
             checkpoint_root: str | None = None) -> dict:
    """Run one sweep cell at every replicate seed; returns the JSON record.

    A sharded cell below the crossover runs on the fleet plane
    (:func:`_pick_executor`); the record's ``"executor"`` is the plane that
    ran.  ``checkpoint_root`` (durable sweeps) gives each replicate seed a
    round-checkpoint directory under it.
    """
    if not len(seeds):
        raise ValueError("run_cell needs at least one replicate seed")
    cell = _pick_executor(cell)
    cache_before = plan_cache.stats() if plan_cache is not None else None
    t0 = time.time()
    results = run_replicates_loop(cell.spec, seeds, plan_cache,
                                  checkpoint_root=checkpoint_root)
    wall = time.time() - t0

    # Per-cell plan-cache delta: how much of this cell's control plane was
    # replayed vs replanned (sweep cache efficacy in the perf trajectory).
    cache_stats = None
    if plan_cache is not None:
        after = plan_cache.stats()
        cache_stats = {"hits": after["hits"] - cache_before["hits"],
                       "misses": after["misses"] - cache_before["misses"],
                       "entries": after["entries"]}

    ledger = results[0].ledger            # seed-independent by construction
    curves = [r.accuracy for r in results]
    return {
        "label": cell.label,
        "axis": cell.axis,
        "value": cell.value,
        "strategy": cell.strategy,
        "executor": resolve_engine(cell.spec.fl).mode,
        "plan_cache": cache_stats,
        "seeds": [int(s) for s in seeds],
        "accuracy": curves,
        "loss": [r.loss for r in results],
        "summary": artifacts.summarize_curves(curves),
        "diffusion_rounds": list(results[0].diffusion_rounds),
        "iid_distance": [float(x) for x in results[0].iid_distance],
        "comm": {
            "subframes": int(ledger.subframes),
            "transmitted_models": int(ledger.transmitted_models),
            "transmitted_bits": float(ledger.transmitted_bits),
            "pusch_bandwidth_hz_s": float(ledger.bandwidth_hz_s),  # Eq. 15
            "uplink_models": int(ledger.uplink_models),
            "downlink_models": int(ledger.downlink_models),
            "energy_j": float(getattr(ledger, "energy_j", 0.0)),
        },
        "wall_clock_s": wall,
    }


def run_sweep(name: str, smoke: bool = True, seeds: Sequence[int] = (0,),
              out_dir: str | None = "auto",
              engine: EngineSpec | str = "host",
              plan_cache: PlanCache | None = None,
              checkpoint_every: int = 0, resume: bool = False,
              state_dir: str | None = None,
              log=None, **spec_overrides) -> dict:
    """Expand a registered sweep, run every cell, write the BENCH artifact.

    Args:
      name: registry key (``fig3_alpha`` … ``table2_strategies``).
      smoke: smoke-sized grid (CPU-minutes) vs full grid.
      seeds: replicate seeds; curves are reported per seed.
      out_dir: where ``BENCH_feddif_<name>.json`` is written; the default
        ``"auto"`` resolves through
        :func:`repro.experiments.artifacts.default_out_dir` (the single
        artifact directory CI globs); ``None`` skips writing (used by tests
        and by callers composing artifacts).
      engine: an :class:`~repro.fl.engine.EngineSpec` or an
        :data:`~repro.fl.engine.ENGINE_PRESETS` name, stamped as
        ``FLConfig.engine`` on every cell (a sweep whose axis is the engine
        overrides it per cell).  With ``planner="jax"`` the whole sweep's
        diffusion plans are computed up front in batched device calls
        (:func:`prepopulate_plan_cache`); the per-cell runs then replay
        them from the shared cache.
      plan_cache: share one across sweeps if desired; default is a fresh
        cache per sweep (still shared across all cells *and* seeds).
      checkpoint_every: round-checkpoint cadence R.  Any of
        ``checkpoint_every > 0``, ``resume`` or ``state_dir`` makes the
        sweep **durable**: a work-queue manifest, per-cell round
        checkpoints and finished-cell records live under ``state_dir``
        (default ``<artifact dir>/sweeps/<name>``), a crashing cell is
        marked failed and isolated while the rest of the grid completes,
        and a killed sweep is restartable with ``resume=True`` —
        reproducing the *identical* BENCH artifact (modulo wall-clock; see
        :func:`repro.experiments.artifacts.strip_volatile`).
      resume: continue a previous durable run from its manifest: done cells
        load their stored records, failed cells are retried, interrupted
        cells restart from their latest round checkpoint.
      state_dir: durable-state directory override.
      spec_overrides: forwarded to ``SweepDef.expand`` (e.g. tiny
        ``num_samples`` in tests).

    Returns the artifact dict (also written to disk unless out_dir=None).
    """
    defn = get_sweep(name)
    if isinstance(engine, str):
        engine = EngineSpec.preset(engine)
    cells = expand_sweep(name, smoke=smoke, engine=engine, **spec_overrides)
    cache = plan_cache if plan_cache is not None else PlanCache()
    durable = checkpoint_every > 0 or resume or state_dir is not None

    manifest = None
    if durable:
        from repro.experiments import durability
        state_dir = state_dir or durability.default_state_dir(name)
        os.makedirs(state_dir, exist_ok=True)
        config = {"sweep": name, "smoke": smoke,
                  "seeds": [int(s) for s in seeds],
                  "executor": engine.mode, "planner": engine.planner,
                  "engine": engine.describe(),
                  "checkpoint_every": int(checkpoint_every),
                  "spec_overrides": spec_overrides}
        manifest = durability.SweepManifest.open(
            state_dir, name, config, [c.label for c in cells], resume)
        if checkpoint_every <= 0:
            # resume without an explicit cadence: adopt the stored one.
            checkpoint_every = int(
                manifest.data["config"].get("checkpoint_every") or 0) or 1
        if resume and durability.load_plan_cache_file(state_dir, cache):
            if log is not None:
                log(f"{name},plan_cache,restored="
                    f"{cache.stats()['entries']}")

    t0 = time.time()
    if engine.planner == "jax":
        pre = prepopulate_plan_cache(cells, cache)
        if log is not None:
            log(f"{name},preplan,planned={pre['planned']},"
                f"batches={pre['batches']},sec={time.time() - t0:.1f}")
        if manifest is not None:
            from repro.experiments import durability
            durability.save_plan_cache_file(state_dir, cache)

    records = []
    for cell in cells:
        if manifest is not None:
            if manifest.status(cell.label) == "done":
                records.append(manifest.load_record(cell.label))
                if log is not None:
                    log(f"{name},{cell.label},resumed=done")
                continue
            manifest.mark(cell.label, "running")
            cell = cell.with_fl(checkpoint_every=int(checkpoint_every))
            ckpt_root = manifest.cell_checkpoint_root(cell.label)
            try:
                rec = run_cell(cell, seeds, plan_cache=cache,
                               checkpoint_root=ckpt_root)
            except Exception as e:          # noqa: BLE001 — cell isolation
                # One broken cell must not sink the grid: record the error,
                # keep going.  Preempted/KeyboardInterrupt (BaseException)
                # still abort the whole sweep.
                manifest.mark(cell.label, "failed",
                              error=f"{type(e).__name__}: {e}")
                if log is not None:
                    log(f"{name},{cell.label},FAILED={type(e).__name__}")
                continue
            manifest.store_record(cell.label, rec)
            manifest.mark(cell.label, "done")
            from repro.experiments import durability
            durability.save_plan_cache_file(state_dir, cache)
            rec = manifest.load_record(cell.label)  # canonical JSON types
        else:
            rec = run_cell(cell, seeds, plan_cache=cache)
        if log is not None:
            s = rec["summary"]
            log(f"{name},{rec['label']},executor={rec['executor']},"
                f"peak_acc={s['peak_mean']:.4f},"
                f"subframes={rec['comm']['subframes']},"
                f"bandwidth_hz_s={rec['comm']['pusch_bandwidth_hz_s']:.3e},"
                f"sec={rec['wall_clock_s']:.1f}")
        records.append(rec)

    artifact = artifacts.build_artifact(
        sweep_name=name, figure=defn.figure, axis=defn.axis, smoke=smoke,
        seeds=list(seeds), cells=records, executor=engine.mode,
        planner=engine.planner, plan_cache_stats=cache.stats(),
        wall_clock_s=time.time() - t0,
        failed_cells=manifest.failed_cells() if manifest is not None
        else None)
    if manifest is not None:
        artifact["manifest"] = manifest.path
    if out_dir is not None:
        if out_dir == "auto":
            out_dir = artifacts.default_out_dir()
        artifact["path"] = artifacts.write_artifact(artifact, out_dir)
    return artifact
