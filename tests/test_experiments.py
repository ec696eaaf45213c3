"""Sweep registry / orchestrator / plan-cache tests (repro.experiments)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core.auction import AuctionConfig
from repro.core.diffusion import (DiffusionPlanner, PlanCache,
                                  feddif_cache_key, plan_cache_key)
from repro.core.dol import DiffusionState
from repro.experiments import (REGISTRY, bench_path, expand_sweep, run_sweep,
                               sweep_names)
from repro.experiments.replicate import run_replicates_loop
from repro.fl.engine import SHARDED_CROSSOVER_N, EngineSpec, resolve_engine
from repro.fl.experiment import ExperimentSpec
from repro.fl.models import TASK_MODELS
from repro.fl.server import STRATEGIES


# ------------------------------------------------------------------ registry

def test_registry_has_all_paper_sweeps():
    assert set(sweep_names()) >= {"fig3_alpha", "fig4_epsilon",
                                  "fig5_gamma_min", "fig6_tasks",
                                  "table2_strategies"}


@pytest.mark.parametrize("name", sorted(REGISTRY))
@pytest.mark.parametrize("smoke", [True, False])
def test_every_sweep_expands_to_valid_specs(name, smoke):
    cells = expand_sweep(name, smoke=smoke)
    assert cells, name
    labels = [c.label for c in cells]
    assert len(set(labels)) == len(labels), "cell labels must be unique"
    for c in cells:
        assert isinstance(c.spec, ExperimentSpec)
        assert c.spec.fl.strategy in STRATEGIES
        assert c.spec.task in TASK_MODELS
        assert c.spec.alpha > 0
        assert c.spec.fl.rounds >= 1
        assert c.spec.fl.topology_seed is not None
        # The axis value must actually land on the spec.
        got = {"alpha": c.spec.alpha, "epsilon": c.spec.fl.epsilon,
               "gamma_min": c.spec.fl.gamma_min, "task": c.spec.task,
               "strategy": c.spec.fl.strategy,
               "num_clients": c.spec.fl.num_clients,
               "scenario": c.spec.fl.scenario,
               "engine": c.spec.fl.engine}[c.axis]
        assert got == c.value
        if c.axis == "num_clients":   # scaling sweeps keep M = N
            assert c.spec.fl.num_models == c.value


def test_smoke_grid_is_subset_of_full_grid():
    for name in sweep_names():
        d = REGISTRY[name]
        assert set(d.smoke_values) <= set(d.values)


def test_table2_strategy_axis_has_at_least_three_points():
    d = REGISTRY["table2_strategies"]
    assert len(d.values) >= 3
    assert "d2d_random_walk" in d.values
    assert "feddif" in d.values and "fedavg" in d.values


def test_fig7_scaling_targets_large_n_with_churn():
    d = REGISTRY["fig7_scaling"]
    assert d.axis == "num_clients"
    assert max(d.values) >= 256 and max(d.smoke_values) >= 64
    assert d.fl_overrides.get("churn_rate", 0) > 0
    cells = expand_sweep("fig7_scaling", smoke=True, engine="sharded")
    assert all(resolve_engine(c.spec.fl).mode == "sharded" for c in cells)
    assert all(c.spec.fl.churn_rate > 0 for c in cells)


def test_fig7_full_grid_drops_auction_strategies_at_large_n():
    """At N ≥ 1024 the Hungarian auction control plane is O(N³); the sweep's
    ``value_strategies`` override keeps only the auction-free strategies
    there while the ≤256 points still compare against feddif."""
    cells = expand_sweep("fig7_scaling", smoke=False)
    by_n = {}
    for c in cells:
        by_n.setdefault(c.value, set()).add(c.strategy)
    assert max(by_n) >= 4096
    for n, strategies in by_n.items():
        if n >= 1024:
            assert "feddif" not in strategies, n
            assert "d2d_random_walk" in strategies, n
        else:
            assert "feddif" in strategies, n


def test_auto_engine_downgrades_sharded_below_crossover():
    """A sharded cell swaps to fleet under the measured N-crossover (the
    mesh dispatch overhead regime) and keeps sharded at/above it; the chosen
    executor lands in the cell record."""
    from repro.experiments.orchestrator import _pick_executor
    cells = expand_sweep("fig7_scaling", smoke=True,
                         engine=EngineSpec(mode="sharded"))
    for cell in cells:
        picked = _pick_executor(cell)
        want = ("fleet" if cell.spec.fl.num_clients < SHARDED_CROSSOVER_N
                else "sharded")
        assert resolve_engine(picked.spec.fl).mode == want, cell.label
    # cells on the other planes are left alone
    for cell in expand_sweep("fig7_scaling", smoke=True, engine="host"):
        assert _pick_executor(cell) is cell


def test_run_cell_records_downgraded_executor():
    from repro.experiments.orchestrator import run_cell
    cell = next(c for c in expand_sweep(
        "fig7_scaling", smoke=True, engine=EngineSpec(mode="sharded"),
        num_samples=400)
        if c.strategy == "fedavg" and c.value == 20)
    cell = dataclasses.replace(
        cell, spec=dataclasses.replace(
            cell.spec, fl=dataclasses.replace(cell.spec.fl, rounds=1)))
    rec = run_cell(cell, seeds=(0,))
    assert rec["executor"] == "fleet"


def test_expand_overrides_reach_spec():
    cells = expand_sweep("fig3_alpha", smoke=True, num_samples=123)
    assert all(c.spec.num_samples == 123 for c in cells)


# ---------------------------------------------------------------- plan cache

def _tiny_partition(n=4, c=5, seed=0):
    rng = np.random.default_rng(seed)
    dsi = rng.dirichlet(np.ones(c), size=n).astype(np.float32)
    sizes = rng.integers(50, 100, size=n).astype(np.float64)
    return dsi, sizes


def _seed_state(m, n, dsi, sizes):
    state = DiffusionState.init(m, n, dsi.shape[1])
    for mi in range(m):
        h = int(state.holder[mi])
        state.record_training(mi, h, dsi[h], float(sizes[h]))
    return state


def test_plan_cache_hit_replays_plan_and_state():
    dsi, sizes = _tiny_partition()
    n = m = 4
    cache = PlanCache()
    key = plan_cache_key(7, 0, dsi, sizes, 0.04, 1.0, "w1_norm",
                         extra=(n, m))

    planner = DiffusionPlanner(epsilon=0.04)
    s1 = _seed_state(m, n, dsi, sizes)
    rng1 = np.random.default_rng([7, 0])
    plan1 = planner.plan_communication_round(s1, dsi, sizes, rng1,
                                             cache=cache, cache_key=key)
    assert cache.stats() == {"hits": 0, "misses": 1, "entries": 1}

    s2 = _seed_state(m, n, dsi, sizes)
    rng2 = np.random.default_rng([7, 0])
    plan2 = planner.plan_communication_round(s2, dsi, sizes, rng2,
                                             cache=cache, cache_key=key)
    assert cache.stats()["hits"] == 1
    assert plan2 is plan1                      # replayed, not replanned
    np.testing.assert_array_equal(s1.holder, s2.holder)
    np.testing.assert_allclose(s1.dol, s2.dol)
    np.testing.assert_array_equal(s1.visited, s2.visited)


def test_plan_cache_key_distinguishes_inputs():
    dsi, sizes = _tiny_partition()
    k1 = plan_cache_key(0, 0, dsi, sizes, 0.04, 1.0, "w1_norm")
    assert k1 == plan_cache_key(0, 0, dsi.copy(), sizes.copy(), 0.04, 1.0,
                                "w1_norm")
    assert k1 != plan_cache_key(0, 1, dsi, sizes, 0.04, 1.0, "w1_norm")
    assert k1 != plan_cache_key(0, 0, dsi, sizes, 0.1, 1.0, "w1_norm")
    assert k1 != plan_cache_key(0, 0, dsi, sizes, 0.04, 2.0, "w1_norm")
    dsi2 = dsi.copy()
    dsi2[0, 0] += 0.25
    assert k1 != plan_cache_key(0, 0, dsi2, sizes, 0.04, 1.0, "w1_norm")


def test_feddif_cache_key_separates_the_engine_planners():
    """Host and jax plans never share a cache line, whichever way the
    engine is given."""
    dsi, sizes = _tiny_partition()
    fl = expand_sweep("fig3_alpha", smoke=True)[0].spec.fl
    keys = [feddif_cache_key(dataclasses.replace(fl, engine=engine), 0, dsi,
                             sizes, 1e6, AuctionConfig())
            for engine in (EngineSpec(planner="host"), "host",
                           EngineSpec(planner="jax"),
                           EngineSpec(mode="fleet", planner="jax"))]
    assert keys[0] == keys[1] != keys[2] == keys[3]


def test_plan_cache_lru_eviction():
    dsi, sizes = _tiny_partition()
    cache = PlanCache(max_entries=2)
    planner = DiffusionPlanner(epsilon=0.04)
    for t in range(3):
        key = plan_cache_key(0, t, dsi, sizes, 0.04, 1.0, "w1_norm")
        s = _seed_state(4, 4, dsi, sizes)
        planner.plan_communication_round(s, dsi, sizes,
                                         np.random.default_rng([0, t]),
                                         cache=cache, cache_key=key)
    assert len(cache) == 2


# ------------------------------------------------------------- replication

def _tiny_cells(name="fig3_alpha"):
    return expand_sweep(name, smoke=True, num_samples=300)


def test_preplanned_jax_cell_replays_from_the_cache():
    """A FedDif cell whose engine plans on the device is planned up front,
    and its run replays every round from the cache."""
    from repro.experiments.orchestrator import (prepopulate_plan_cache,
                                                run_cell)
    cell = next(c for c in expand_sweep(
        "fig3_alpha", smoke=True, num_samples=300,
        engine=EngineSpec(planner="jax")) if c.strategy == "feddif")
    cache = PlanCache()
    pre = prepopulate_plan_cache([cell], cache)
    assert pre["planned"] == cell.spec.fl.rounds and pre["skipped"] == 0
    rec = run_cell(cell, (0,), cache)
    assert rec["plan_cache"]["hits"] >= 1
    assert rec["plan_cache"]["misses"] == 0


def test_replicate_seeds_differ_on_data_plane():
    cell = next(c for c in _tiny_cells() if c.strategy == "fedavg")
    r = run_replicates_loop(cell.spec, (0, 1))
    assert r[0].config.seed == 0 and r[1].config.seed == 1
    # Same communication (control plane shared) ...
    assert r[0].ledger.as_dict() == r[1].ledger.as_dict()
    # ... but different models (init seeds differ).
    assert r[0].accuracy != r[1].accuracy


def test_sweep_cli_builds_one_engine_for_every_cell(monkeypatch):
    """``--engine`` names a preset and ``--planner`` sets its planner: the
    two become one EngineSpec on every cell; ``--executor`` is refused."""
    from repro.launch import sweep
    seen = []

    def fake_run_sweep(name, smoke, seeds, out_dir, engine, **kw):
        seen.extend(resolve_engine(c.spec.fl)
                    for c in expand_sweep(name, smoke=smoke, engine=engine))
        return {"plan_cache": {}, "cells": [], "path": "-",
                "wall_clock_s": 0.0}

    monkeypatch.setattr(sweep, "run_sweep", fake_run_sweep)
    monkeypatch.setattr(sweep, "enable_compile_cache", lambda: None)
    assert sweep.main(["--sweep", "fig7_scaling", "--engine", "sharded",
                       "--planner", "jax", "--out-dir", "-"]) == 0
    assert seen and set(seen) == {EngineSpec(mode="sharded", planner="jax")}
    with pytest.raises(SystemExit):
        sweep.main(["--sweep", "fig7_scaling", "--executor", "sharded"])


# ----------------------------------------------------------- end-to-end + IO

def test_smallest_sweep_end_to_end_writes_valid_artifact(tmp_path):
    art = run_sweep("fig5_gamma_min", smoke=True, seeds=(0,),
                    out_dir=str(tmp_path), num_samples=300)
    path = bench_path("fig5_gamma_min", str(tmp_path))
    assert art["path"] == path
    on_disk = json.load(open(path))
    assert on_disk["sweep"] == "fig5_gamma_min"
    assert on_disk["axis"] == "gamma_min"
    assert on_disk["mode"] == "smoke"
    assert on_disk["plan_cache"]["misses"] >= 1
    assert len(on_disk["cells"]) == len(REGISTRY["fig5_gamma_min"]
                                        .smoke_values)
    assert on_disk["executor"] == "host"
    for c in on_disk["cells"]:
        assert c["accuracy"] and c["accuracy"][0], "per-seed accuracy curve"
        assert c["summary"]["peak_mean"] is not None
        assert c["comm"]["subframes"] > 0
        assert "pusch_bandwidth_hz_s" in c["comm"]
        assert c["wall_clock_s"] >= 0
        assert c["executor"] == "host"
        # per-cell plan-cache delta (sweep cache efficacy trajectory)
        pc = c["plan_cache"]
        assert set(pc) == {"hits", "misses", "entries"}
        assert pc["hits"] + pc["misses"] >= 1


# ------------------------------------------------- durability: RNG streams

def test_checkpoint_audits_every_rng_stream_position(tmp_path, monkeypatch):
    """The round checkpoint must carry every RNG stream position the run
    consumes: the per-client data-shuffle cursors and the model-seed
    bit-generator state.  (The control-plane and churn streams are stateless
    ``[seed, t, tag]`` draws and need no stored position.)  An interrupted
    run's checkpoint at step k must equal a clean run's checkpoint at the
    same step, byte for byte on these fields."""
    from repro.fl.experiment import run_experiment
    from repro.fl.resume import Preempted, RoundCheckpointer
    from repro.fl.server import FLConfig
    from repro.train import load_metadata, valid_steps

    fl = FLConfig(strategy="feddif", num_clients=4, num_models=4, rounds=3,
                  topology_seed=None, churn_rate=0.25, batch_size=8,
                  checkpoint_every=1, local_epochs=2)
    spec = ExperimentSpec(task="logistic", num_samples=400, fl=fl)

    clean_dir = str(tmp_path / "clean")
    run_experiment(spec, checkpoint_dir=clean_dir)

    killed_dir = str(tmp_path / "killed")
    with monkeypatch.context() as m:
        m.setattr(RoundCheckpointer, "fail_after_save", 1)
        with pytest.raises(Preempted):
            run_experiment(spec, checkpoint_dir=killed_dir)
    run_experiment(spec, checkpoint_dir=killed_dir)

    steps = valid_steps(clean_dir)
    assert steps and steps == valid_steps(killed_dir)
    for step in steps:
        a = load_metadata(clean_dir, step)
        b = load_metadata(killed_dir, step)
        # data-shuffle stream: per-client epoch cursors, advanced by
        # local_epochs per training session — nonzero and exactly restored
        assert a["extra"]["loader_epochs"] == b["extra"]["loader_epochs"]
        assert any(e > 0 for e in a["extra"]["loader_epochs"])
        # model-seed stream: full PCG64 bit-generator state (exact 128-bit
        # ints — JSON carries Python ints losslessly)
        assert a["rng_state"] == b["rng_state"]
        # and the cumulative Eq.-15 ledger
        assert a["ledger"] == b["ledger"]


def test_loader_epoch_cursor_replays_batch_order():
    from repro.data.pipeline import ClientLoader

    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    y = np.arange(20) % 4
    a = ClientLoader(x, y, batch_size=4, seed=11)
    for _ in range(3):
        list(a.epoch())
    assert a.epochs_drawn == 3
    reference = [b["x"].tolist() for b in a.epoch()]

    b = ClientLoader(x, y, batch_size=4, seed=11)
    b.seek(3)                       # resume path repositions the stream
    replay = [bb["x"].tolist() for bb in b.epoch()]
    assert replay == reference


def test_plan_cache_state_dict_roundtrip_replays():
    """PlanCache state_dict/load_state_dict round-trips entries, counters
    and plan contents — the durable sweep's plan_cache.json contract."""
    from repro.core.diffusion import PlanCache

    cache = PlanCache()
    cell = next(c for c in _tiny_cells() if c.strategy == "feddif")
    run_replicates_loop(cell.spec, (0,), cache)
    assert cache.stats()["entries"] >= 1

    state = json.loads(json.dumps(cache.state_dict()))   # disk round-trip
    restored = PlanCache.from_state_dict(state)
    assert restored.stats() == cache.stats()

    # replaying from the restored cache reproduces the identical run
    r_orig = run_replicates_loop(cell.spec, (0,), PlanCache())
    r_rest = run_replicates_loop(cell.spec, (0,), restored)
    assert r_rest[0].accuracy == r_orig[0].accuracy
    assert r_rest[0].ledger == r_orig[0].ledger


# --------------------------------------------- durability: artifact writes

def test_bench_write_is_atomic_under_partial_write(tmp_path, monkeypatch):
    """Kill the writer mid-serialization: the previous artifact must remain
    intact on disk (temp+rename — no torn JSON)."""
    import repro.train.checkpoint as ckpt_mod
    from repro.experiments.artifacts import bench_file, write_bench_json

    write_bench_json("torn", {"generation": 1}, str(tmp_path))
    real_dump = json.dump

    def dying_dump(obj, f, **kw):
        f.write('{"generation": 2, "partial": [1, 2')   # torn bytes
        raise OSError("disk full mid-write")

    with monkeypatch.context() as m:
        m.setattr(ckpt_mod.json, "dump", dying_dump)
        with pytest.raises(OSError):
            write_bench_json("torn", {"generation": 2}, str(tmp_path))

    with open(bench_file("torn", str(tmp_path))) as f:
        assert json.load(f) == {"generation": 1}        # old bytes intact
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    assert json.dump is real_dump


def test_artifact_always_reports_failed_cells(tmp_path):
    art = run_sweep("fig5_gamma_min", smoke=True, seeds=(0,),
                    out_dir=str(tmp_path), num_samples=300)
    assert art["failed_cells"] == []                    # key always present
    on_disk = json.load(open(bench_path("fig5_gamma_min", str(tmp_path))))
    assert on_disk["failed_cells"] == []


def test_strip_volatile_drops_only_run_dependent_fields(tmp_path):
    from repro.experiments import strip_volatile
    art = run_sweep("fig5_gamma_min", smoke=True, seeds=(0,),
                    out_dir=str(tmp_path), num_samples=300)
    s = strip_volatile(art)
    for k in ("created_unix", "wall_clock_s", "plan_cache", "path"):
        assert k not in s
    for c in s["cells"]:
        assert "wall_clock_s" not in c and "plan_cache" not in c
        assert c["comm"]["subframes"] > 0               # physics retained
    assert s["failed_cells"] == []
