"""Benchmark harness — one function per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Emits ``name,value,derived`` CSV lines per benchmark plus a summary.  Quick
mode (default) shrinks rounds/clients so the whole suite runs on a laptop
CPU in minutes; ``--full`` approaches the paper's settings.

The figure/table sweeps (fig3–fig6, table2) are driven by the declarative
sweep registry in ``repro.experiments`` — the same grids the
``python -m repro.launch.sweep`` CLI runs — so sweep definitions live in one
place; this file only adds presentation (CSV lines, rounds-to-target).
Each registry-driven bench also writes its ``BENCH_feddif_<sweep>.json``
artifact under ``benchmarks/results/``.

Paper artifacts covered:
  fig2_convergence      IID-distance & diffusion-efficiency convergence
                        (analytical Eq. 30 vs experimental)
  fig3_alpha_sweep      accuracy / diffusion rounds / comms vs Dirichlet α
  fig4_epsilon_sweep    minimum tolerable IID distance ε
  fig5_qos_sweep        minimum tolerable QoS γ_min
  fig6_tasks            ML-task sweep (logistic/svm/fcn/lstm/cnn)
  table1_accuracy       FedDif vs baselines, accuracy after T rounds
  table2_comm_eff       sub-frames / transmitted models to target accuracy
  fig_async_sweep       sync vs buffered-async engines (fig_async registry)
  async_throughput      buffered-async vs barrier: virtual time-to-target
  kernels_microbench    flash-attn / stc / ssm-scan op timings (XLA path)
  roofline_summary      aggregates benchmarks/results dry-run JSONs
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import numpy as np

sys.path.insert(0, "src")


ENGINE = None          # set by --engine and --planner: the EngineSpec
                       # stamped on every figure/table cell (None = host)


def _fl(strategy, alpha=1.0, rounds=6, clients=8, task="fcn", **kw):
    from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
    engine = ENGINE or EngineSpec()
    if kw.get("underlay"):
        # the jax planner does not model underlay CUE interference
        engine = dataclasses.replace(engine, planner="host")
    spec = ExperimentSpec(
        task=task, alpha=alpha, num_samples=4000,
        fl=FLConfig(strategy=strategy, rounds=rounds, num_clients=clients,
                    num_models=clients, seed=0, engine=engine, **kw))
    return run_experiment(spec)


def fig2_convergence(full: bool):
    """Fig. 2: IID distance converges to 0 with diffusion; per-α mixing."""
    import jax.numpy as jnp
    from repro.core import dol as D
    rows = []
    for alpha in ([0.1, 0.5, 1.0, 100.0] if full else [0.1, 1.0]):
        rng = np.random.default_rng(0)
        c, iters = 10, 30
        er = []
        dol = jnp.zeros((c,))
        chain = 0.0
        for k in range(iters):
            dsi = rng.dirichlet(np.ones(c) * alpha).astype(np.float32)
            size = float(rng.integers(100, 500))
            dol, chain = D.update_dol(dol, chain, jnp.asarray(dsi), size)
            er.append(float(D.iid_distance(dol)))
        rows.append((alpha, er[0], er[4], er[-1]))
        print(f"fig2_convergence,alpha={alpha},iid_k1={er[0]:.4f},"
              f"iid_k5={er[4]:.4f},iid_k{iters}={er[-1]:.4f}")
    return rows


def _run_registry_sweep(bench_name: str, sweep_name: str, full: bool):
    """Drive one registry sweep; print per-cell CSV lines; write artifact."""
    from repro.experiments import run_sweep
    art = run_sweep(sweep_name, smoke=not full, seeds=(0,),
                    engine=ENGINE or "host")
    for c in art["cells"]:
        curve = np.mean(np.asarray(c["accuracy"]), axis=0)
        print(f"{bench_name},{c['label']},executor={c['executor']},"
              f"acc={float(np.max(curve)):.4f},"
              f"dif_rounds={np.mean(c['diffusion_rounds']):.1f},"
              f"subframes={c['comm']['subframes']},"
              f"models={c['comm']['transmitted_models']},"
              f"bandwidth_hz_s={c['comm']['pusch_bandwidth_hz_s']:.3e},"
              f"sec={c['wall_clock_s']:.0f}", flush=True)
    return art


def fig3_alpha_sweep(full: bool):
    _run_registry_sweep("fig3_alpha_sweep", "fig3_alpha", full)


def fig4_epsilon_sweep(full: bool):
    _run_registry_sweep("fig4_epsilon_sweep", "fig4_epsilon", full)


def fig5_qos_sweep(full: bool):
    _run_registry_sweep("fig5_qos_sweep", "fig5_gamma_min", full)


def fig6_tasks(full: bool):
    _run_registry_sweep("fig6_tasks", "fig6_tasks", full)


def fig_async_sweep(full: bool):
    """fig_async registry sweep: buffered-async vs barrier-on-the-event-
    queue (both arms share the straggler/link-delay model and 5% churn)."""
    _run_registry_sweep("fig_async_sweep", "fig_async", full)


def fig_scenarios_sweep(full: bool):
    """fig_scenarios registry sweep: strategy × wireless-world scenario
    (static / mobile / multicell / energy_capped) — accuracy plus the
    ledger (incl. TX joules) per cell."""
    _run_registry_sweep("fig_scenarios_sweep", "fig_scenarios", full)


def world_step(full: bool):
    """Steady-state throughput of the vmapped world transition — the pure
    ``channels.world.step`` pytree update the mobile planner folds into its
    jitted while_loop — plus the host/jax static-placement parity flag.
    Writes ``BENCH_world_step.json`` (gated in benchmarks/budgets.json)."""
    import jax
    import jax.numpy as jnp
    from repro.channels.topology import CellTopology
    from repro.channels.world import WorldConfig, init_world, step
    from repro.experiments.artifacts import write_bench_json

    n = 256 if full else 64
    batch = 64
    cfg = WorldConfig.for_scenario("mobile")
    topo = CellTopology(num_pues=n)
    rng = np.random.default_rng(0)
    worlds = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[init_world(cfg, topo, np.random.default_rng([0, i]), n)
          for i in range(batch)])

    stepper = jax.jit(jax.vmap(
        lambda w: step(w, step_m=cfg.step_m)))
    worlds = jax.block_until_ready(stepper(worlds))   # compile
    iters = 200 if full else 50
    t0 = time.time()
    w = worlds
    for _ in range(iters):
        w = stepper(w)
    jax.block_until_ready(w)
    dt = time.time() - t0
    steps_per_s = batch * iters / dt

    # Host/jax twin parity on the polar placement transform (the seam the
    # static scenario's bit-identity rests on).
    r = 250.0 * np.sqrt(rng.uniform(size=n))
    theta = rng.uniform(0.0, 2 * np.pi, size=n)
    host = CellTopology.positions_from_polar(r, theta, xp=np)
    dev = CellTopology.positions_from_polar(jnp.asarray(r),
                                            jnp.asarray(theta), xp=jnp)
    parity_ok = bool(np.allclose(host, np.asarray(dev), atol=1e-5))

    record = {"steps_per_s": float(steps_per_s), "parity_ok": parity_ok,
              "batch": batch, "num_clients": n, "iters": iters}
    print(f"world_step,vmapped_{batch}x{n},{steps_per_s:.0f},steps_per_s,"
          f"parity_ok={parity_ok}", flush=True)
    write_bench_json("world_step", record)


def async_throughput(full: bool):
    """Buffered-async round plane throughput (the PR-9 tentpole headline).

    Two arms of the same event-driven executor on the same cell — fedavg at
    fleet scale under lognormal compute stragglers and channel-drawn D2D/
    uplink link delays (the ``async`` / ``async_barrier`` EngineSpec
    presets):

    * ``async_barrier``: K = all — every server tick waits for the slowest
      arrival, i.e. the classic synchronous round on the virtual clock;
    * ``async``: FedBuff-style buffering — aggregate the first
      K = 0.5·M arrivals per tick with the staleness discount
      ``alpha/(1+s)^beta``, park the rest in the buffer.

    Both arms replay identical schedules, so their Eq.-15 ledgers are
    asserted bit-identical — the *only* difference is when the virtual
    clock advances.  Headline numbers: ``speedup_time_to_target``
    (virtual seconds to the shared target accuracy, barrier/buffered;
    budget-gated ≥ 1.5x at N ≥ 256) and arrivals aggregated per virtual
    second.  Emits ``BENCH_async_throughput.json``."""
    from repro.experiments.artifacts import write_bench_json
    from repro.fl import ExperimentSpec, FLConfig, run_experiment

    n = 256 if full else 64
    rounds = 6 if full else 4
    samples = 5 * n          # comm/straggler-dominated regime: tiny shards

    def run_arm(preset):
        spec = ExperimentSpec(
            task="fcn", alpha=0.5, num_samples=samples,
            fl=FLConfig(strategy="fedavg", rounds=rounds, num_clients=n,
                        num_models=n, seed=0, topology_seed=0,
                        eval_every=1, engine=preset))
        t0 = time.time()
        r = run_experiment(spec)
        dt = time.time() - t0
        h = r.history
        vfinal = float(h.virtual_s[-1])
        arrivals = int(np.sum(h.arrivals))
        print(f"async_throughput,engine={preset},clients={n},"
              f"rounds={rounds},sec={dt:.1f},virtual_s={vfinal:.2f},"
              f"arrivals={arrivals},"
              f"arrivals_per_vs={arrivals / max(vfinal, 1e-9):.2f},"
              f"acc={max(h.accuracy):.4f},"
              f"mean_staleness={np.mean(h.staleness):.2f},"
              f"ticks={len(h.virtual_s)}", flush=True)
        return r, {"engine": preset, "wall_clock_s": dt,
                   "virtual_s": vfinal, "arrivals": arrivals,
                   "arrivals_per_vs": arrivals / max(vfinal, 1e-9),
                   "peak_acc": float(max(h.accuracy)),
                   "mean_staleness": float(np.mean(h.staleness)),
                   "ticks": len(h.virtual_s)}

    r_barrier, arm_barrier = run_arm("async_barrier")
    r_async, arm_async = run_arm("async")
    ledger_parity = (r_barrier.ledger.as_dict() == r_async.ledger.as_dict())
    assert ledger_parity, \
        "both arms replay identical schedules; Eq.-15 ledgers must agree"

    # Shared target both arms reach: just under the weaker arm's peak.
    target = 0.98 * min(arm_barrier["peak_acc"], arm_async["peak_acc"])
    tta_barrier = r_barrier.time_to_accuracy(target)
    tta_async = r_async.time_to_accuracy(target)
    speedup = float(tta_barrier) / max(float(tta_async), 1e-9)
    record = {
        "clients": n, "rounds": rounds, "num_samples": samples,
        "arms": {"async_barrier": arm_barrier, "async": arm_async},
        "ledger_parity": ledger_parity,
        "target_acc": target,
        "time_to_target_barrier_vs": tta_barrier,
        "time_to_target_async_vs": tta_async,
        "speedup_time_to_target": speedup,
        "throughput_gain": (arm_async["arrivals_per_vs"]
                            / max(arm_barrier["arrivals_per_vs"], 1e-9)),
        "max_wall_clock_s": max(arm_barrier["wall_clock_s"],
                                arm_async["wall_clock_s"]),
    }
    write_bench_json("async_throughput", record)
    print(f"async_throughput,clients={n},target_acc={target:.4f},"
          f"tta_barrier_vs={tta_barrier:.2f},tta_async_vs={tta_async:.2f},"
          f"speedup_time_to_target={speedup:.2f}x,"
          f"throughput_gain={record['throughput_gain']:.2f}x,"
          f"ledger_parity={ledger_parity}", flush=True)


def table1_accuracy(full: bool):
    rounds = 25 if full else 6
    for strat in ["fedavg", "tthf", "stc", "fedswap", "feddif"]:
        r = _fl(strat, alpha=1.0, rounds=rounds)
        print(f"table1_accuracy,strategy={strat},"
              f"acc={max(r.accuracy):.4f},final={r.accuracy[-1]:.4f}",
              flush=True)


def table2_comm_eff(full: bool):
    """Sub-frames / transmitted models until target accuracy (the paper's
    80 % CNN target, rescaled to this synthetic task).  The grid comes from
    the ``table2_strategies`` registry entry (incl. d2d_random_walk)."""
    art = _run_registry_sweep("table2_comm_eff", "table2_strategies", full)
    cells = {c["strategy"]: c for c in art["cells"]}
    base = cells.get("fedavg")
    if base is None:
        return
    base_curve = np.mean(np.asarray(base["accuracy"]), axis=0)
    target = float(np.max(base_curve))   # baseline peak = target (Sec. VI-A)
    print(f"table2_comm_eff,target_acc={target:.4f},source=fedavg_peak")
    for strat, c in cells.items():
        curve = np.mean(np.asarray(c["accuracy"]), axis=0)
        hit = next((i + 1 for i, a in enumerate(curve) if a >= target), None)
        frac = (hit / len(curve)) if hit else 1.0   # ledger is cumulative
        comm = c["comm"]
        print(f"table2_comm_eff,strategy={strat},"
              f"rounds_to_target={hit if hit else 'n/a'},"
              f"subframes={int(comm['subframes']*frac)},"
              f"models={int(comm['transmitted_models']*frac)},"
              f"bits={comm['transmitted_bits']*frac:.3e}", flush=True)


def planner_speedup(full: bool):
    """Control-plane hot path: sequential host planner (Python while +
    O(n³) Hungarian per diffusion round) vs the batched jax planner (one
    vmapped device call planning every cell × round; Bertsekas auction in
    lax.while_loop).  ≥8 concurrent cells at N=20 clients; asserts plan
    *equivalence* (identical round/hop counts and total Eq.-17 decrement —
    exact hop lists are reported but may differ on Eq.-38 ties) and emits
    BENCH_planner_speedup.json."""
    from repro.core import DiffusionPlanner, DiffusionState
    from repro.core.planner import (decode_plan, plan_round_inputs,
                                    plan_rounds_batched)
    from repro.experiments.artifacts import write_bench_json

    n = m = 20
    c = 10
    n_cells = 16 if full else 8
    rounds_per_cell = 2
    max_rounds = 24

    def build_cell(cell_idx):
        rng = np.random.default_rng(cell_idx)
        dsi = rng.dirichlet(np.ones(c) * 0.5, n).astype(np.float32)
        sizes = rng.integers(200, 800, n).astype(np.float64)
        return dsi, sizes

    def init_state(dsi, sizes):
        state = DiffusionState.init(m, n, c)
        for mi in range(m):
            state.record_training(mi, mi % n, dsi[mi % n],
                                  float(sizes[mi % n]))
        return state

    planner = DiffusionPlanner(epsilon=0.04, max_rounds=max_rounds)
    jplanner = DiffusionPlanner(epsilon=0.04, max_rounds=max_rounds,
                                mode="jax")
    cells = [build_cell(i) for i in range(n_cells)]
    topo = planner.topology

    # ---- host loop: one sequential auction loop per cell × round --------
    t0 = time.time()
    host_plans = []
    for i, (dsi, sizes) in enumerate(cells):
        for t in range(rounds_per_cell):
            rng = np.random.default_rng([i, t])
            pos = topo.sample_positions(rng, n)
            host_plans.append(planner.plan_communication_round(
                init_state(dsi, sizes), dsi, sizes, rng, positions=pos))
    host_s = time.time() - t0

    # ---- batched jax: all cells × rounds in one device call -------------
    def batch_inputs():
        items = []
        for i, (dsi, sizes) in enumerate(cells):
            for t in range(rounds_per_cell):
                rng = np.random.default_rng([i, t])
                pos = topo.sample_positions(rng, n)
                inp, g64 = plan_round_inputs(jplanner, init_state(dsi, sizes),
                                             dsi, sizes, rng, positions=pos)
                items.append((inp, g64))
        return items

    t0 = time.time()
    items = batch_inputs()
    outs = plan_rounds_batched([inp for inp, _ in items], metric="w1_norm",
                               allow_retraining=False)
    jax_cold_s = time.time() - t0            # includes compile
    t0 = time.time()
    items = batch_inputs()
    outs = plan_rounds_batched([inp for inp, _ in items], metric="w1_norm",
                               allow_retraining=False)
    jax_plans = [decode_plan(o, num_models=m, gamma_seq64=g64,
                             model_bits=jplanner.auction.model_bits)
                 for o, (_, g64) in zip(outs, items)]
    jax_s = time.time() - t0                 # steady state (compile cached)

    # Equivalence: identical round/hop structure and identical total
    # IID-distance decrement.  Exact hop lists can differ when several
    # matchings tie on Eq.-38 total weight (Hungarian and auction break
    # ties differently; at N=20 a few rounds do tie) — reported, but not a
    # failure.  Strict hop-list parity is asserted at the default config
    # in tests/test_planner_jax.py.
    hops_equal = all(
        [(h.model, h.src, h.dst, h.round_index) for h in ph.hops]
        == [(h.model, h.src, h.dst, h.round_index) for h in pj.hops]
        for ph, pj in zip(host_plans, jax_plans))
    plans_equivalent = all(
        ph.num_rounds == pj.num_rounds and len(ph.hops) == len(pj.hops)
        and abs(sum(h.decrement for h in ph.hops)
                - sum(h.decrement for h in pj.hops))
        <= 1e-6 * max(sum(h.decrement for h in ph.hops), 1e-12)
        for ph, pj in zip(host_plans, jax_plans))
    speedup = host_s / max(jax_s, 1e-9)
    record = {
        "clients": n, "models": m, "cells": n_cells,
        "rounds_per_cell": rounds_per_cell, "max_diffusion_rounds": max_rounds,
        "host_s": host_s, "jax_s": jax_s, "jax_cold_s": jax_cold_s,
        "speedup": speedup, "hops_equal": hops_equal,
        "plans_equivalent": plans_equivalent,
        "total_hops": sum(len(p.hops) for p in host_plans),
    }
    write_bench_json("planner_speedup", record)
    print(f"planner_speedup,cells={n_cells},clients={n},"
          f"host_s={host_s:.2f},jax_s={jax_s:.2f},"
          f"jax_cold_s={jax_cold_s:.2f},speedup={speedup:.2f}x,"
          f"hops_equal={hops_equal},plans_equivalent={plans_equivalent}",
          flush=True)
    assert plans_equivalent, \
        "host and jax planners must produce equivalent plans"
    assert speedup > 1.0, "batched jax planner should beat the host loop"


def executor_speedup(full: bool):
    """RoundSchedule executor seam: same cell, host vs fleet data plane.

    The schedule (and therefore the ledger) is identical by construction;
    the fleet executor replaces the per-client Python loop (one jitted call
    per client per batch, with a host sync per step) by one vmapped call per
    batch over the whole client-stacked fleet — the wall-clock gap is pure
    dispatch/sync overhead and grows with fleet size."""
    from repro.fl import ExperimentSpec, FLConfig, run_experiment
    clients = 32 if full else 20
    rounds = 4 if full else 3
    rows = {}
    for executor in ("host", "fleet"):
        spec = ExperimentSpec(
            task="fcn", alpha=1.0, num_samples=6000,
            fl=FLConfig(strategy="feddif", rounds=rounds,
                        num_clients=clients, num_models=clients, seed=0,
                        topology_seed=0, engine=executor))
        t0 = time.time()
        r = run_experiment(spec)
        dt = time.time() - t0
        rows[executor] = (dt, r)
        print(f"executor_speedup,executor={executor},clients={clients},"
              f"rounds={rounds},sec={dt:.1f},acc={max(r.accuracy):.4f},"
              f"subframes={r.ledger.subframes}", flush=True)
    host_t, host_r = rows["host"]
    fleet_t, fleet_r = rows["fleet"]
    assert host_r.ledger.as_dict() == fleet_r.ledger.as_dict(), \
        "executors must charge identical schedules"
    speedup = host_t / max(fleet_t, 1e-9)
    from repro.experiments.artifacts import write_bench_json
    write_bench_json("executor_speedup", {
        "clients": clients, "rounds": rounds,
        "host_s": host_t, "fleet_s": fleet_t, "speedup": speedup,
        "ledger_identical": True,
    })
    print(f"executor_speedup,speedup={speedup:.2f}x,"
          f"ledger_identical=True", flush=True)


def fleet_scaling(full: bool):
    """Large-N data planes: ``fleet`` (single-device client-stacked vmap) vs
    ``sharded`` (shard_map over the 2-D ``("clients", "model")`` mesh) at
    growing N, with the ``host`` reference run at the smallest N for
    three-way bit-identical ledger parity and a ``sharded`` arm with
    ``shard_overlap="off"`` at the largest N isolating the fused
    comm/compute-overlapped round plane's win over the op-by-op plane.
    Schedules/ledgers are executor-independent by construction, so the
    comparison signal is the **data plane's** steady-state wall-clock —
    ``FLResult.round_wall_s`` with the first (compile) round dropped; the
    shared host control plane (planner, schedule build) is excluded by
    construction.  Up to N=256 the task is the paper's CNN under FedDif;
    at N≥1024 the Hungarian auction control plane is O(N³), so the data
    plane is exercised with the auction-free ``d2d_random_walk`` diffusion
    on the FCN, with the per-client shard pinned small so the round is
    comm-dominated (the fleet-scale regime the overlap targets).  Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` for a K-device
    CPU mesh (``main()`` forces K=2 when this bench runs standalone; CI's
    mesh2d job uses K=8); on one device the planes are the same program
    and the speedup checks are skipped (also skipped by the budget gate
    via ``device_count``).  Also emits the
    :mod:`benchmarks.roofline` readout for one round at the largest N
    (achieved FLOP/s and wire bytes vs the machine's measured GEMM peak).
    Emits ``BENCH_fleet_scaling.json``.
    """
    import jax
    from benchmarks.roofline import fl_round_roofline, measure_machine_peak
    from repro.experiments.artifacts import write_bench_json
    from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
    from repro.fl.experiment import load_experiment_data, spec_model_bits

    n_devices = len(jax.devices())
    sizes = (20, 64, 256, 1024) if full else (20, 64)
    # Small-N arms run 4 rounds; the N≥1024 arms run 6.  The fused sharded
    # plane compiles one program per round *signature*; signature
    # normalization (step-count padding + hop-wave bucketing, see
    # ``ShardedFleetExecutor``) bounds steady state to two signatures, but
    # their compiles can land as late as rounds 1 and 3 — with fewer
    # rounds min(round_wall_s[1:]) would report a compile, not steady
    # state.  min (not mean) is the steady statistic: forced multi-device
    # CPU meshes oversubscribe the host and collective rendezvous can
    # stall a round by whole seconds, on either plane.
    rounds = 4
    big_rounds = 6
    big_n = max(sizes)

    def make_spec(n, executor, rounds=None, overlap="auto"):
        # experiment.py trains on the test_frac side of the split, so this
        # is ~40 train samples (2–3 batches) per client up to N=150.  At
        # N≥1024 the per-client shard is pinned small (5 rows/client): at
        # fleet scale the round is comm-dominated — D2D hop traffic, not
        # local SGD, sets the wall-clock — which is the regime the
        # overlapped plane exists for (and the one the roofline reports).
        task = "cnn" if n <= 256 else "fcn"
        strategy = "feddif" if n <= 256 else "d2d_random_walk"
        if rounds is None:
            rounds = 4 if n <= 256 else big_rounds
        return ExperimentSpec(
            task=task, alpha=0.5,
            num_samples=min(200 * n, 30000) if n <= 256 else 5 * n,
            fl=FLConfig(strategy=strategy, rounds=rounds, num_clients=n,
                        num_models=n, seed=0, topology_seed=0,
                        max_diffusion_rounds=6 if n <= 256 else 3,
                        engine=EngineSpec(mode=executor,
                                          shard_overlap=overlap)))

    arms = []
    for n in sizes:
        if n == sizes[0]:
            arms.append((n, "host", make_spec(n, "host")))
        arms.append((n, "fleet", make_spec(n, "fleet")))
        arms.append((n, "sharded", make_spec(n, "sharded")))
    arms.append((big_n, "sharded_off",
                 make_spec(big_n, "sharded", overlap="off")))

    cells, ledgers, results = [], {}, {}
    for n, label, spec in arms:
        t0 = time.time()
        r = run_experiment(spec)
        dt = time.time() - t0
        steady = min(r.round_wall_s[1:])
        ledgers[(n, label)] = r.ledger.as_dict()
        results[(n, label)] = r
        cells.append({"clients": n, "executor": label,
                      "task": spec.task, "strategy": spec.fl.strategy,
                      "wall_clock_s": dt, "round_s": steady,
                      "acc": max(r.accuracy),
                      "subframes": r.ledger.subframes})
        print(f"fleet_scaling,clients={n},executor={label},"
              f"sec={dt:.1f},round_s={steady:.2f},"
              f"acc={max(r.accuracy):.4f},"
              f"subframes={r.ledger.subframes}", flush=True)
    n0 = sizes[0]
    ledger_parity = (ledgers[(n0, "host")] == ledgers[(n0, "fleet")]
                     == ledgers[(n0, "sharded")])
    assert ledger_parity, "host/fleet/sharded must charge identical ledgers"
    assert all(ledgers[(n, "fleet")] == ledgers[(n, "sharded")]
               for n in sizes), "fleet/sharded ledgers must agree at every N"
    assert ledgers[(big_n, "sharded_off")] == ledgers[(big_n, "sharded")], \
        "overlap on/off must charge the identical schedule"
    by = {(c["clients"], c["executor"]): c["round_s"] for c in cells}
    speedups = {n: by[(n, "fleet")] / max(by[(n, "sharded")], 1e-9)
                for n in sizes}
    overlap_speedup = (by[(big_n, "sharded_off")]
                       / max(by[(big_n, "sharded")], 1e-9))

    # --- roofline readout for one steady round at the largest N on the
    # overlapped sharded arm: analytic FLOPs/bytes (Eq. 15 ledger terms)
    # vs the machine's measured GEMM peak.
    spec = make_spec(big_n, "sharded")
    big_arm_rounds = spec.fl.rounds
    _, _, part, _ = load_experiment_data(spec, with_loaders=False)
    r = results[(big_n, "sharded")]
    led = ledgers[(big_n, "sharded")]
    hops = float(np.mean(r.diffusion_rounds))
    roofline = fl_round_roofline(
        param_count=spec_model_bits(spec) / spec.fl.bits_per_param,
        train_rows=float(np.sum(part.data_sizes)) * (1.0 + hops),
        clients=big_n,
        d2d_models=(led["transmitted_models"] - led["uplink_models"])
        / big_arm_rounds,
        uldl_models=(led["uplink_models"] + led["downlink_models"])
        / big_arm_rounds,
        round_s=by[(big_n, "sharded")],
        bits_per_param=spec.fl.bits_per_param,
        peak_flops=measure_machine_peak())
    print(f"fleet_scaling,roofline,clients={big_n},"
          f"achieved_gflops={roofline['achieved_flops']/1e9:.2f},"
          f"peak_gflops={roofline['machine_peak_flops']/1e9:.2f},"
          f"utilization={roofline['utilization']:.4f},"
          f"wire_mb_per_round={roofline['round_bytes_moved']/1e6:.1f}",
          flush=True)

    record = {
        "device_count": n_devices, "host_cpus": os.cpu_count() or 1,
        "sizes": list(sizes), "rounds": rounds,
        "big_n_rounds": big_arm_rounds,
        "cells": cells, "ledger_parity": ledger_parity,
        "speedup_by_n": {str(n): s for n, s in speedups.items()},
        "speedup_at_scale": speedups[big_n], "scale_n": big_n,
        "overlap_speedup": overlap_speedup, "overlap_scale_n": big_n,
        "roofline": roofline,
        "max_wall_clock_s": max(c["wall_clock_s"] for c in cells),
    }
    write_bench_json("fleet_scaling", record)
    print(f"fleet_scaling,devices={n_devices},"
          f"steady_speedup_n{big_n}={speedups[big_n]:.2f}x,"
          f"overlap_speedup_n{big_n}={overlap_speedup:.2f}x,"
          f"ledger_parity={ledger_parity}", flush=True)
    if speedups[big_n] <= 0.85 and n_devices > 1:
        # check_budgets (benchmarks/budgets.json) is the regression gate;
        # the in-bench hard failure is scoped to the topology the 0.85
        # floor was calibrated on — a forced 2-device CPU mesh with at
        # least 2 host cores behind it.  With forced devices oversubscribing
        # a single core there is no parallelism to win, only dispatch and
        # collective-rendezvous overhead to pay (fleet's single-device vmap
        # pays neither), so the comparison reports instead of aborting the
        # benches queued after this one.
        msg = (f"sharded far behind fleet at N={big_n} on a "
               f"{n_devices}-device mesh (got {speedups[big_n]:.2f}x)")
        if (n_devices == 2 and jax.default_backend() == "cpu"
                and (os.cpu_count() or 1) >= 2):
            raise AssertionError(msg)
        print(f"fleet_scaling,WARNING,{msg}", flush=True)


def lm_hops(full: bool):
    """FedDif-over-LMs hop-payload bench (the adapter hop plane).

    Three payload arms on the small LoRA transformer (``task="lm"``) under
    FedDif: ``full_f32`` (adapter view off — every D2D hop moves the whole
    fp32 model), ``adapter_f32`` (hops move only the trainable LoRA
    adapter, base broadcast once at round 0) and ``adapter_int8`` (adapter
    hops additionally cross the wire int8-packed via the
    ``quant_pack``/``quant_unpack`` kernel pair).  Each arm runs on all
    three executors — host / fleet / sharded — and their Eq.-15 ledgers
    must be *bit-identical per arm*; the ledger's ``transmitted_bits`` must
    also decompose exactly into
    ``uplinks·view_f32_bits + d2d_hops·hop_bits`` with the analytic
    ``spec_adapter_bits`` figures, so the measured wire volume and the
    analytic payload model cannot drift apart.  Headline numbers:
    bytes-per-hop per arm, the full_f32/adapter_int8 payload reduction
    (budget-gated ≥ 50x), the int8-vs-f32 accuracy gap (≤ 2 pts absolute)
    and the steady-round wall-clock (``min(round_wall_s[1:])`` on the
    fleet plane) per arm.  The roofline readout reports the int8 arm with
    ``d2d_bits`` so the bytes side reflects the packed wire.  Emits
    ``BENCH_lm_hops.json``."""
    import jax
    from benchmarks.roofline import fl_round_roofline, measure_machine_peak
    from repro.experiments.artifacts import write_bench_json
    from repro.fl import ExperimentSpec, FLConfig, run_experiment
    from repro.fl.experiment import spec_adapter_bits, spec_model_bits

    n_devices = len(jax.devices())
    clients = 8
    rounds = 6 if full else 3
    samples = 4096 if full else 1536

    def make_spec(executor, adapter_hops, hop_quant):
        return ExperimentSpec(
            task="lm", alpha=0.5, dim=32, num_samples=samples,
            adapter_hops=adapter_hops,
            fl=FLConfig(strategy="feddif", rounds=rounds,
                        num_clients=clients, num_models=clients, seed=0,
                        topology_seed=0, max_diffusion_rounds=4,
                        engine=executor, hop_quant=hop_quant))

    # arm -> (adapter_hops, hop_quant); full_f32 is the no-view baseline.
    arms = {"full_f32": (False, "none"),
            "adapter_f32": (True, "none"),
            "adapter_int8": (True, "int8")}
    executors = ("host", "fleet", "sharded")

    cells = []
    arm_stats = {}
    ledger_parity = True
    ledger_bits_match = True
    for arm, (adapter_hops, hop_quant) in arms.items():
        spec0 = make_spec("host", adapter_hops, hop_quant)
        hop_bits = spec_adapter_bits(spec0)          # what one D2D hop moves
        view_f32_bits = spec_adapter_bits(           # what one uplink moves
            dataclasses.replace(
                spec0, fl=dataclasses.replace(spec0.fl, hop_quant="none")))
        ledgers, results = {}, {}
        for executor in executors:
            spec = make_spec(executor, adapter_hops, hop_quant)
            t0 = time.time()
            r = run_experiment(spec)
            dt = time.time() - t0
            ledgers[executor] = r.ledger.as_dict()
            results[executor] = r
            steady = min(r.round_wall_s[1:])
            cells.append({"arm": arm, "executor": executor,
                          "wall_clock_s": dt, "round_s": steady,
                          "acc": max(r.accuracy),
                          "subframes": r.ledger.subframes,
                          "transmitted_bits": r.ledger.transmitted_bits})
            print(f"lm_hops,arm={arm},executor={executor},sec={dt:.1f},"
                  f"round_s={steady:.2f},acc={max(r.accuracy):.4f},"
                  f"bits={r.ledger.transmitted_bits:.3e}", flush=True)
        parity = (ledgers["host"] == ledgers["fleet"] == ledgers["sharded"])
        ledger_parity &= parity
        led = ledgers["host"]
        d2d_hops = led["transmitted_models"] - led["uplink_models"]
        expected = (led["uplink_models"] * view_f32_bits
                    + d2d_hops * hop_bits)
        bits_match = bool(np.isclose(led["transmitted_bits"], expected,
                                     rtol=1e-9, atol=0.0))
        ledger_bits_match &= bits_match
        arm_stats[arm] = {
            "hop_bits": hop_bits, "bytes_per_hop": hop_bits / 8.0,
            "view_f32_bits": view_f32_bits, "d2d_hops": d2d_hops,
            "uplink_models": led["uplink_models"],
            "downlink_models": led["downlink_models"],
            "transmitted_bits": led["transmitted_bits"],
            "acc": max(results["host"].accuracy),
            "round_s": min(results["fleet"].round_wall_s[1:]),
            "ledger_parity": parity, "ledger_bits_match": bits_match,
        }
        print(f"lm_hops,arm={arm},bytes_per_hop={hop_bits / 8.0:.0f},"
              f"d2d_hops={d2d_hops},ledger_parity={parity},"
              f"ledger_bits_match={bits_match}", flush=True)
    assert ledger_parity, \
        "host/fleet/sharded must charge identical ledgers per arm"
    assert ledger_bits_match, \
        "measured transmitted_bits must match the analytic payload model"

    reduction_int8 = (arm_stats["full_f32"]["hop_bits"]
                      / arm_stats["adapter_int8"]["hop_bits"])
    reduction_f32 = (arm_stats["full_f32"]["hop_bits"]
                     / arm_stats["adapter_f32"]["hop_bits"])
    acc_gap = abs(arm_stats["adapter_int8"]["acc"]
                  - arm_stats["adapter_f32"]["acc"])
    assert reduction_int8 >= 50.0, \
        f"int8 adapter hops must be >=50x smaller (got {reduction_int8:.1f}x)"

    # Roofline for one steady int8-arm round: d2d_bits carries the packed
    # wire so bytes-moved reflects what the transport actually ships.
    spec = make_spec("fleet", True, "int8")
    st = arm_stats["adapter_int8"]
    roofline = fl_round_roofline(
        param_count=spec_model_bits(spec) / spec.fl.bits_per_param,
        train_rows=float(samples) * (1.0 - spec.test_frac),
        clients=clients,
        d2d_models=st["d2d_hops"] / rounds,
        uldl_models=(st["uplink_models"] + st["downlink_models"]) / rounds,
        round_s=st["round_s"],
        bits_per_param=spec.fl.bits_per_param,
        d2d_bits=st["hop_bits"],
        peak_flops=measure_machine_peak())

    record = {
        "device_count": n_devices, "host_cpus": os.cpu_count() or 1,
        "clients": clients, "rounds": rounds, "num_samples": samples,
        "cells": cells, "arms": arm_stats,
        "ledger_parity": ledger_parity,
        "ledger_bits_match": ledger_bits_match,
        "payload_reduction_int8": reduction_int8,
        "payload_reduction_f32": reduction_f32,
        "acc_gap_int8_vs_f32": acc_gap,
        "roofline": roofline,
        "max_wall_clock_s": max(c["wall_clock_s"] for c in cells),
    }
    write_bench_json("lm_hops", record)
    print(f"lm_hops,payload_reduction_int8={reduction_int8:.1f}x,"
          f"payload_reduction_f32={reduction_f32:.1f}x,"
          f"acc_gap={acc_gap:.4f},ledger_parity={ledger_parity},"
          f"ledger_bits_match={ledger_bits_match}", flush=True)


def kernel_data_plane(full: bool):
    """FL diffusion data-plane kernels (kernels/diffusion.py): parity of
    the Pallas bodies (interpret mode) against the reference twins, and the
    measurable XLA-side win — the planner's fused bid contraction vs the
    (M, N, C) broadcast composite it replaces.  The mix/aggregate flat
    kernel is timed for the record (its one-HBM-pass claim is a TPU
    property; on CPU the dispatcher keeps the per-leaf chain, which is
    also timed here as the baseline)."""
    import jax
    import jax.numpy as jnp
    from repro.core.dol import iid_distance_candidates
    from repro.experiments.artifacts import write_bench_json
    from repro.kernels import ops
    from repro.kernels.diffusion import dol_bid_scores_xla_fused

    rng = np.random.default_rng(0)
    reps, trials = (10, 8) if full else (5, 5)

    def timeit(f, *args):
        # min over trials: robust to scheduler noise on shared CI cores
        jax.block_until_ready(f(*args))
        best = float("inf")
        for _ in range(trials):
            t0 = time.time()
            for _ in range(reps):
                jax.block_until_ready(f(*args))
            best = min(best, (time.time() - t0) / reps)
        return best

    # --- planner bid tensor: broadcast composite vs fused contraction ---
    m, n, c = (512, 8192, 10) if full else (256, 4096, 10)
    dol = jnp.asarray(rng.dirichlet(np.ones(c), size=m), jnp.float32)
    chain = jnp.asarray(rng.integers(1, 500, size=m), jnp.float32)
    dsi = jnp.asarray(rng.dirichlet(np.ones(c), size=n), jnp.float32)
    sizes = jnp.asarray(rng.integers(1, 300, size=n), jnp.float32)
    composite = jax.jit(lambda *a: iid_distance_candidates(*a))
    fused = jax.jit(dol_bid_scores_xla_fused)
    bids_parity = bool(np.allclose(np.asarray(composite(dol, chain, dsi,
                                                        sizes)),
                                   np.asarray(fused(dol, chain, dsi,
                                                    sizes)), atol=2e-5))
    t_comp = timeit(composite, dol, chain, dsi, sizes)
    t_fused = timeit(fused, dol, chain, dsi, sizes)
    bids_speedup = t_comp / max(t_fused, 1e-9)
    print(f"kernel_data_plane,dol_bids,M={m},N={n},C={c},"
          f"composite_us={t_comp*1e6:.0f},fused_us={t_fused*1e6:.0f},"
          f"speedup={bids_speedup:.2f}x", flush=True)

    # --- mix/aggregate: per-leaf chain (ref) vs flat kernel pass ---
    cc = 64 if full else 32
    params = {"l1": jnp.asarray(rng.normal(size=(cc, 784, 64)), jnp.float32),
              "b1": jnp.asarray(rng.normal(size=(cc, 64)), jnp.float32),
              "l2": jnp.asarray(rng.normal(size=(cc, 64, 10)), jnp.float32),
              "b2": jnp.asarray(rng.normal(size=(cc, 10)), jnp.float32)}
    w = jnp.asarray(rng.random((cc, cc)), jnp.float32)
    chain_fn = jax.jit(lambda p, w: ops.mix_aggregate_tree(
        p, w, implementation="ref"))
    t_mix_ref = timeit(chain_fn, params, w)
    # interpret-mode parity of the fused pass (not timed: interpret is a
    # correctness vehicle, not a performance mode)
    fused_tree = ops.mix_aggregate_tree(params, w,
                                        implementation="pallas_interpret")
    mix_parity = all(
        np.allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(chain_fn(params, w)),
                        jax.tree.leaves(fused_tree)))
    # stc hop compression parity on the same stacked fleet
    refp = jax.tree.map(lambda x: x[0], params)
    mask = jnp.asarray(rng.random(cc) < 0.5)
    from repro.distributed.fedshard import masked_stc_compress
    stc_ref = masked_stc_compress(params, refp, mask, 0.01,
                                  implementation="ref")
    stc_pal = masked_stc_compress(params, refp, mask, 0.01,
                                  implementation="pallas_interpret")
    stc_parity = all(
        np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)
        for a, b in zip(jax.tree.leaves(stc_ref), jax.tree.leaves(stc_pal)))
    parity_ok = bool(bids_parity and mix_parity and stc_parity)
    print(f"kernel_data_plane,mix_ref_us={t_mix_ref*1e6:.0f},"
          f"parity_ok={parity_ok}", flush=True)
    write_bench_json("kernel_data_plane", {
        "bids_m": m, "bids_n": n, "bids_c": c,
        "bids_composite_s": t_comp, "bids_fused_s": t_fused,
        "bids_speedup": bids_speedup,
        "mix_clients": cc, "mix_ref_s": t_mix_ref,
        "parity_ok": parity_ok,
    })


def kernels_microbench(full: bool):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    shapes = [(1, 512, 4, 64)] if not full else [(1, 512, 4, 64),
                                                 (2, 2048, 8, 64)]
    for shp in shapes:
        q = jax.random.normal(key, shp, jnp.float32)
        f = jax.jit(lambda a: ops.flash_attention(a, a, a,
                                                  implementation="xla"))
        f(q).block_until_ready()
        t0 = time.time()
        for _ in range(5):
            f(q).block_until_ready()
        us = (time.time() - t0) / 5 * 1e6
        print(f"kernels_microbench,flash_attention_xla_{shp},{us:.0f},"
              f"us_per_call")
    x = jax.random.normal(key, (1 << 20,), jnp.float32)
    g = jax.jit(lambda a: ops.stc_compress(a, 0.01, implementation="xla"))
    g(x).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        g(x).block_until_ready()
    print(f"kernels_microbench,stc_compress_xla_1M,"
          f"{(time.time()-t0)/5*1e6:.0f},us_per_call")
    da = jnp.exp(-jax.random.uniform(key, (2, 1024, 128, 16)))
    h = jax.jit(lambda a: ops.ssm_scan(a, a, implementation="xla"))
    h(da).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        h(da).block_until_ready()
    print(f"kernels_microbench,ssm_scan_xla_2x1024x128x16,"
          f"{(time.time()-t0)/5*1e6:.0f},us_per_call")


def roofline_summary(full: bool):
    import glob
    import json
    from benchmarks.roofline import analyze
    files = sorted(glob.glob("benchmarks/results/dryrun_*.json"))
    if not files:
        print("roofline_summary,no_results,0,run repro.launch.dryrun first")
        return
    ok = err = skip = 0
    for path in files:
        rec = json.load(open(path))
        st = rec.get("status")
        ok += st == "ok"
        err += st == "error"
        skip += st == "skipped"
        row = analyze(rec)
        if row:
            print(f"roofline_summary,{row['arch']}/{row['shape']}/"
                  f"{row['mesh']},{row['dominant']},"
                  f"c={row['t_compute_s']:.2e}s m={row['t_memory_s']:.2e}s "
                  f"x={row['t_collective_s']:.2e}s "
                  f"useful={row['useful_flop_ratio']:.2f}")
    print(f"roofline_summary,totals,ok={ok},err={err} skip={skip}")


def appendix_scenarios(full: bool):
    """Appendix C: fully-decentralized (Fig 7), probability distances
    (Fig 8), re-trainable FedDif (Fig 10), underlay D2D (Fig 12)."""
    rounds = 12 if full else 4
    base = _fl("feddif", alpha=0.5, rounds=rounds)
    print(f"appendixC,scenario=baseline,acc={max(base.accuracy):.4f},"
          f"subframes={base.ledger.subframes}")
    gossip = _fl("gossip", alpha=0.5, rounds=rounds)
    print(f"appendixC,scenario=fully_decentralized,"
          f"acc={max(gossip.accuracy):.4f},"
          f"subframes={gossip.ledger.subframes}")
    for metric in ["kld", "jsd"]:
        r = _fl("feddif", alpha=0.5, rounds=rounds, metric=metric)
        print(f"appendixC,scenario=metric_{metric},"
              f"acc={max(r.accuracy):.4f},"
              f"dif_rounds={np.mean(r.diffusion_rounds):.1f}")
    retr = _fl("feddif", alpha=0.5, rounds=rounds, allow_retraining=True,
               max_diffusion_rounds=12)
    print(f"appendixC,scenario=retrainable,acc={max(retr.accuracy):.4f},"
          f"dif_rounds={np.mean(retr.diffusion_rounds):.1f},"
          f"subframes={retr.ledger.subframes}")
    under = _fl("feddif", alpha=0.5, rounds=rounds, underlay=True)
    print(f"appendixC,scenario=underlay,acc={max(under.accuracy):.4f},"
          f"subframes={under.ledger.subframes} "
          f"(vs overlay {base.ledger.subframes})")


BENCHES = [fig2_convergence, fig3_alpha_sweep, fig4_epsilon_sweep,
           fig5_qos_sweep, fig6_tasks, fig_async_sweep, fig_scenarios_sweep,
           async_throughput, table1_accuracy, table2_comm_eff,
           planner_speedup, executor_speedup, fleet_scaling, lm_hops,
           kernel_data_plane, world_step, appendix_scenarios,
           kernels_microbench, roofline_summary]


def check_budgets(budgets_path: str = "benchmarks/budgets.json") -> int:
    """Perf-regression gate: compare every BENCH artifact named in
    ``benchmarks/budgets.json`` against its budgeted metrics.

    Budget schema — one entry per gate::

        {"<gate>": {"artifact": "BENCH_x.json",
                    "checks": [{"key": "a.b", "min": 1.0, "tolerance": 0.1},
                               {"key": "flag", "equals": true},
                               {"key": "speedup", "min": 1.0,
                                "when": {"key": "device_count", "gte": 2}}]}}

    ``min``/``max`` checks fail when the artifact value crosses the budget
    beyond the relative ``tolerance`` (``value < min·(1−tol)`` resp.
    ``value > max·(1+tol)``); ``equals`` checks are exact.  ``key`` is a
    dotted path into the artifact JSON.  An optional ``when`` guard — one
    condition dict or a list of them, all of which must hold — skips a
    check unless the named artifact fields satisfy every bound given
    (``gte`` and/or ``lte``) — e.g. speedup gates only bind on the exact
    device count and minimum host core count they were calibrated
    against.  A missing artifact is a
    failure — the gate exists so CI cannot silently stop producing the
    number.  Returns a process exit code (0 = within budget).
    """
    import json
    from repro.experiments.artifacts import default_out_dir

    def lookup(art, dotted):
        value = art
        for part in dotted.split("."):
            value = value[part]
        return value

    with open(budgets_path) as f:
        budgets = json.load(f)
    failures = []
    for gate, entry in sorted(budgets.items()):
        path = os.path.join(default_out_dir(), entry["artifact"])
        if not os.path.exists(path):
            failures.append(f"{gate}: missing artifact {path} "
                            f"(did the bench run?)")
            continue
        with open(path) as f:
            art = json.load(f)
        for chk in entry["checks"]:
            conds = chk.get("when")
            if isinstance(conds, dict):
                conds = [conds]
            skip = None
            for cond in conds or ():
                try:
                    guard = lookup(art, cond["key"])
                    if "gte" in cond and not guard >= cond["gte"]:
                        skip = f"{cond['key']}<{cond['gte']}"
                        break
                    if "lte" in cond and not guard <= cond["lte"]:
                        skip = f"{cond['key']}>{cond['lte']}"
                        break
                except (KeyError, TypeError):
                    pass        # guard field absent: check applies
            if skip is not None:
                print(f"budget_skip,{gate},{chk['key']},{skip}",
                      flush=True)
                continue
            try:
                value = lookup(art, chk["key"])
            except (KeyError, TypeError):
                failures.append(f"{gate}: key {chk['key']!r} missing "
                                f"from {path}")
                continue
            tol = float(chk.get("tolerance", 0.0))
            if "equals" in chk and value != chk["equals"]:
                failures.append(f"{gate}: {chk['key']} == {value!r}, "
                                f"budget requires {chk['equals']!r}")
            elif "min" in chk and value < chk["min"] * (1.0 - tol):
                failures.append(f"{gate}: {chk['key']} = {value:.4g} below "
                                f"budget min {chk['min']}·(1−{tol})")
            elif "max" in chk and value > chk["max"] * (1.0 + tol):
                failures.append(f"{gate}: {chk['key']} = {value:.4g} above "
                                f"budget max {chk['max']}·(1+{tol})")
            else:
                print(f"budget_ok,{gate},{chk['key']},{value}", flush=True)
    for f_ in failures:
        print(f"BUDGET REGRESSION: {f_}", flush=True)
    print(f"# check_budgets: {len(failures)} violation(s)", flush=True)
    return 1 if failures else 0


def _force_cpu_mesh_for(bench_names: list) -> None:
    """fleet_scaling / lm_hops need >1 device to mean anything; force a
    2-device CPU mesh when only multi-device benches are selected (CI runs
    them standalone), XLA_FLAGS has no explicit count yet, and jax has not
    been imported (the flag is read at first import).  Full-suite runs are
    left on the real device topology — forcing virtual devices there would
    time every other bench under a configuration its budget was not
    calibrated for; the speedup budget checks are gated on the artifact's
    ``device_count``."""
    flags = os.environ.get("XLA_FLAGS", "")
    if (bench_names and set(bench_names) <= {"fleet_scaling", "lm_hops"}
            and "jax" not in sys.modules
            and "xla_force_host_platform_device_count" not in flags):
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()


def main() -> None:
    global ENGINE
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--engine", default="host",
                    help="EngineSpec preset stamped on every figure/table "
                         "cell (host/fleet/sharded/auto/async/async_barrier;"
                         " executor_speedup / fleet_scaling / "
                         "async_throughput always compare)")
    ap.add_argument("--planner", choices=["host", "jax"], default="host",
                    help="FL control plane for the figure/table benches "
                         "(planner_speedup always compares both)")
    ap.add_argument("--check-budgets", action="store_true",
                    help="run no benches; gate existing BENCH artifacts "
                         "against benchmarks/budgets.json and exit nonzero "
                         "on regression")
    args = ap.parse_args()
    if args.check_budgets:
        raise SystemExit(check_budgets())
    selected = [b.__name__ for b in BENCHES
                if not args.only or args.only in b.__name__]
    _force_cpu_mesh_for(selected)   # must precede any repro/jax import
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.fl.engine import ENGINE_PRESETS
    if args.engine not in ENGINE_PRESETS:
        raise SystemExit(f"--engine must be one of {sorted(ENGINE_PRESETS)}")
    ENGINE = dataclasses.replace(ENGINE_PRESETS[args.engine],
                                 planner=args.planner)
    t0 = time.time()
    for bench in BENCHES:
        if bench.__name__ not in selected:
            continue
        print(f"# === {bench.__name__} ===", flush=True)
        bench(args.full)
    print(f"# total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
