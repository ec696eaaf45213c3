"""Kill/resume fault-injection harness for the durable sweep orchestration.

The contract under test (ISSUE: durable, fault-tolerant sweeps): a sweep
killed at **any** round boundary — in-process simulated preemption
(:class:`repro.fl.resume.Preempted`) or a real SIGTERM to a CLI subprocess —
and restarted with ``resume=True`` produces **bit-identical** results to an
uninterrupted run: same Eq.-15 ledger, same accuracy/loss curves, same final
parameters, same BENCH artifact modulo wall-clock
(:func:`repro.experiments.artifacts.strip_volatile`).  And a cell that
*crashes* (raises) is isolated: marked failed in the manifest while the rest
of the grid completes, retried on resume.
"""
import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.experiments import strip_volatile
from repro.experiments.durability import SweepManifest
from repro.experiments.orchestrator import run_sweep
from repro.fl.engine import EngineSpec
from repro.fl.experiment import ExperimentSpec, run_experiment
from repro.fl.resume import Preempted, RoundCheckpointer
from repro.fl.server import FLConfig

ROUNDS = 4


def _spec(executor: str, strategy: str = "feddif", **fl_overrides
          ) -> ExperimentSpec:
    kwargs = dict(strategy=strategy, num_clients=4, num_models=4,
                  rounds=ROUNDS, topology_seed=7, engine=executor,
                  checkpoint_every=1, batch_size=8)
    kwargs.update(fl_overrides)
    return ExperimentSpec(task="logistic", num_samples=400,
                          fl=FLConfig(**kwargs))


def _trees_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return (len(la) == len(lb)
            and all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(la, lb)))


def _assert_results_identical(clean, resumed):
    assert clean.accuracy == resumed.accuracy
    assert clean.loss == resumed.loss
    assert clean.ledger == resumed.ledger          # Eq.-15 ledger, exact
    assert clean.diffusion_rounds == resumed.diffusion_rounds
    assert clean.iid_distance == resumed.iid_distance
    assert _trees_equal(clean.final_params, resumed.final_params)


def _run_killed_then_resumed(spec, ckpt_dir, kill_round, monkeypatch):
    """Run the cell, preempting right after round ``kill_round``'s
    checkpoint lands; then resume it to completion."""
    with monkeypatch.context() as m:
        m.setattr(RoundCheckpointer, "fail_after_save", kill_round)
        with pytest.raises(Preempted):
            run_experiment(spec, checkpoint_dir=ckpt_dir)
    return run_experiment(spec, checkpoint_dir=ckpt_dir)


# ------------------------------------------------ experiment-level parity

@pytest.mark.parametrize("executor", ["host", "fleet", "sharded"])
@pytest.mark.parametrize("strategy", ["feddif", "gossip"])
def test_kill_resume_bit_identical(executor, strategy, tmp_path,
                                   monkeypatch):
    """Preempt after the round-2 checkpoint; the resumed run must be
    indistinguishable from one that never died — for the slotless (feddif)
    and persistent-slot (gossip) round structures, on every executor."""
    spec = _spec(executor, strategy)
    clean = run_experiment(spec, checkpoint_dir=str(tmp_path / "clean"))
    resumed = _run_killed_then_resumed(spec, str(tmp_path / "killed"),
                                       kill_round=2, monkeypatch=monkeypatch)
    _assert_results_identical(clean, resumed)


def test_kill_resume_every_boundary(tmp_path, monkeypatch):
    """Every possible kill round (1..rounds-1) resumes bit-identically."""
    spec = _spec("host")
    clean = run_experiment(spec, checkpoint_dir=str(tmp_path / "clean"))
    for k in range(1, ROUNDS):
        resumed = _run_killed_then_resumed(
            spec, str(tmp_path / f"killed{k}"), kill_round=k,
            monkeypatch=monkeypatch)
        _assert_results_identical(clean, resumed)


def test_double_kill_resume(tmp_path, monkeypatch):
    """Die twice (rounds 1 and 3), resume twice — still bit-identical."""
    spec = _spec("host")
    clean = run_experiment(spec, checkpoint_dir=str(tmp_path / "clean"))
    d = str(tmp_path / "killed")
    for k in (1, 3):
        with monkeypatch.context() as m:
            m.setattr(RoundCheckpointer, "fail_after_save", k)
            with pytest.raises(Preempted):
                run_experiment(spec, checkpoint_dir=d)
    resumed = run_experiment(spec, checkpoint_dir=d)
    _assert_results_identical(clean, resumed)


def test_kill_resume_with_stateful_model_rng(tmp_path, monkeypatch):
    """With ``topology_seed=None`` the control plane consumes the *stateful*
    model-seed generator; resume must restore its bit-generator position."""
    spec = _spec("host", topology_seed=None)
    clean = run_experiment(spec, checkpoint_dir=str(tmp_path / "clean"))
    resumed = _run_killed_then_resumed(spec, str(tmp_path / "killed"),
                                       kill_round=2, monkeypatch=monkeypatch)
    _assert_results_identical(clean, resumed)


def test_kill_resume_with_churn(tmp_path, monkeypatch):
    """Churn draws come from the stateless per-round ``[seed, t, tag]``
    stream — a resumed run must reproduce the same dropout masks."""
    spec = _spec("host", churn_rate=0.3)
    clean = run_experiment(spec, checkpoint_dir=str(tmp_path / "clean"))
    resumed = _run_killed_then_resumed(spec, str(tmp_path / "killed"),
                                       kill_round=2, monkeypatch=monkeypatch)
    _assert_results_identical(clean, resumed)


@pytest.mark.parametrize("change", [
    {"gamma_min": 2.5}, {"engine": "fleet"},
    {"engine": EngineSpec(planner="jax")}],
    ids=["gamma_min", "engine_mode", "planner"])
def test_resume_refuses_mismatched_config(change, tmp_path, monkeypatch):
    spec = _spec("host")
    d = str(tmp_path / "ckpt")
    with monkeypatch.context() as m:
        m.setattr(RoundCheckpointer, "fail_after_save", 2)
        with pytest.raises(Preempted):
            run_experiment(spec, checkpoint_dir=d)
    import dataclasses
    other = dataclasses.replace(
        spec, fl=dataclasses.replace(spec.fl, **change))
    with pytest.raises(ValueError, match="different config"):
        run_experiment(other, checkpoint_dir=d)


# ------------------------------------------------------ hypothesis property

def test_property_kill_round_parity(tmp_path, monkeypatch):
    """Property: for a randomly drawn (kill round, executor) the resumed run
    equals the clean one."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    clean = {}

    @hyp.settings(max_examples=6, deadline=None,
                  suppress_health_check=[
                      hyp.HealthCheck.function_scoped_fixture])
    @hyp.given(k=st.integers(min_value=1, max_value=ROUNDS - 1),
               executor=st.sampled_from(["host", "fleet"]))
    def prop(k, executor):
        spec = _spec(executor)
        if executor not in clean:
            clean[executor] = run_experiment(
                spec, checkpoint_dir=str(tmp_path / f"clean-{executor}"))
        resumed = _run_killed_then_resumed(
            spec, str(tmp_path / f"killed-{executor}-{k}-{time.time_ns()}"),
            kill_round=k, monkeypatch=monkeypatch)
        _assert_results_identical(clean[executor], resumed)

    prop()


# ------------------------------------------------------ sweep-level parity

def _durable_sweep(out, state, **kw):
    return run_sweep("fig3_alpha", seeds=(0, 1), out_dir=out,
                     state_dir=state, num_samples=400, **kw)


def test_sweep_kill_resume_artifact_parity(tmp_path, monkeypatch):
    """Kill a durable sweep mid-grid (in-process preemption), resume it; the
    BENCH artifact must match an uninterrupted durable run bit-for-bit after
    stripping volatile fields."""
    clean = _durable_sweep(str(tmp_path / "o1"), str(tmp_path / "s1"),
                           checkpoint_every=1)
    with monkeypatch.context() as m:
        m.setattr(RoundCheckpointer, "fail_after_save", 1)
        with pytest.raises(Preempted):
            _durable_sweep(str(tmp_path / "o2"), str(tmp_path / "s2"),
                           checkpoint_every=1)
    resumed = _durable_sweep(str(tmp_path / "o2"), str(tmp_path / "s2"),
                             resume=True)
    assert json.dumps(strip_volatile(clean), sort_keys=True) \
        == json.dumps(strip_volatile(resumed), sort_keys=True)
    assert resumed["failed_cells"] == []
    # the manifest agrees: every cell done
    man = SweepManifest.load(str(tmp_path / "s2"))
    assert all(c["status"] == "done" for c in man.data["cells"].values())


def test_sweep_failure_isolation_and_retry(tmp_path, monkeypatch):
    """A cell whose run *raises* is marked failed and skipped while the rest
    of the grid completes; a later resume retries it and heals the sweep."""
    from repro.experiments import orchestrator

    real_loop = orchestrator.run_replicates_loop
    clean = _durable_sweep(str(tmp_path / "o1"), str(tmp_path / "s1"),
                           checkpoint_every=1)
    poisoned = clean["cells"][0]["label"]

    def flaky(spec, seeds, plan_cache=None, checkpoint_root=None):
        if spec.fl.strategy == clean["cells"][0]["strategy"] \
                and f"alpha={spec.alpha}" in poisoned:
            raise RuntimeError("injected cell crash")
        return real_loop(spec, seeds, plan_cache=plan_cache,
                         checkpoint_root=checkpoint_root)

    with monkeypatch.context() as m:
        m.setattr(orchestrator, "run_replicates_loop", flaky)
        broken = _durable_sweep(str(tmp_path / "o2"), str(tmp_path / "s2"),
                                checkpoint_every=1)
    assert [f["label"] for f in broken["failed_cells"]] == [poisoned]
    assert "injected cell crash" in broken["failed_cells"][0]["error"]
    # the other cells completed despite the crash
    assert len(broken["cells"]) == len(clean["cells"]) - 1
    # resume retries the failed cell and the artifact heals to parity
    healed = _durable_sweep(str(tmp_path / "o2"), str(tmp_path / "s2"),
                            resume=True)
    assert healed["failed_cells"] == []
    assert json.dumps(strip_volatile(clean), sort_keys=True) \
        == json.dumps(strip_volatile(healed), sort_keys=True)


def test_fresh_sweep_refuses_existing_state_dir(tmp_path):
    _durable_sweep(str(tmp_path / "o"), str(tmp_path / "s"),
                   checkpoint_every=1)
    with pytest.raises(FileExistsError, match="resume"):
        _durable_sweep(str(tmp_path / "o"), str(tmp_path / "s"),
                       checkpoint_every=1)


def test_resume_refuses_mismatched_sweep_config(tmp_path):
    _durable_sweep(str(tmp_path / "o"), str(tmp_path / "s"),
                   checkpoint_every=1)
    with pytest.raises(ValueError, match="different configuration"):
        run_sweep("fig3_alpha", seeds=(0, 1, 2),   # seeds changed
                  out_dir=str(tmp_path / "o"), state_dir=str(tmp_path / "s"),
                  num_samples=400, resume=True)


# ------------------------------------------------------- SIGTERM subprocess

@pytest.mark.skipif(not hasattr(signal, "SIGTERM") or os.name != "posix",
                    reason="POSIX signals required")
def test_sigterm_kill_resume_cli(tmp_path):
    """The real thing: SIGTERM a durable CLI sweep mid-run, resume it with
    ``--resume``, and diff the artifact against an uninterrupted run."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the child runs on the CPU and keeps its compile cache in tmp_path
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    state, out = str(tmp_path / "state"), str(tmp_path / "out")
    args = [sys.executable, "-m", "repro.launch.sweep",
            "--sweep", "fig3_alpha", "--smoke", "--seeds", "2",
            "--checkpoint-every", "1", "--num-samples", "400",
            "--state-dir", state, "--out-dir", out]

    proc = subprocess.Popen(args, env=env, cwd=repo,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        # Wait for durable progress (first committed round checkpoint),
        # then deliver SIGTERM mid-sweep.
        deadline = time.time() + 120
        def committed():
            for root, _, files in os.walk(os.path.join(state, "cells")):
                if any(f.endswith(".json") and f.startswith("ckpt_")
                       for f in files):
                    return True
            return False
        while time.time() < deadline and proc.poll() is None \
                and not committed():
            time.sleep(0.05)
        assert committed(), "no checkpoint ever committed"
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()

    r = subprocess.run(args + ["--resume"], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr

    clean = run_sweep("fig3_alpha", seeds=(0, 1),
                      out_dir=str(tmp_path / "out-clean"),
                      state_dir=str(tmp_path / "state-clean"),
                      checkpoint_every=1, num_samples=400)
    with open(os.path.join(out, "BENCH_feddif_fig3_alpha.json")) as f:
        resumed = json.load(f)
    assert resumed["failed_cells"] == []
    assert json.dumps(strip_volatile(clean), sort_keys=True, default=str) \
        == json.dumps(strip_volatile(resumed), sort_keys=True, default=str)


def test_sweep_cli_exits_nonzero_when_a_cell_fails(tmp_path, monkeypatch,
                                                   capsys):
    """The durable sweep isolates a crashing cell and finishes the grid,
    but the CLI run as a whole reports failure through its exit code."""
    from repro.experiments import orchestrator
    from repro.launch import sweep

    def poisoned(spec, seeds, plan_cache=None, checkpoint_root=None):
        raise RuntimeError("injected cell crash")

    monkeypatch.setattr(orchestrator, "run_replicates_loop", poisoned)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    was = jax.config.jax_compilation_cache_dir
    try:
        rc = sweep.main(["--sweep", "fig3_alpha", "--smoke",
                         "--num-samples", "400", "--checkpoint-every", "1",
                         "--out-dir", str(tmp_path / "out"),
                         "--state-dir", str(tmp_path / "state")])
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert rc == 1
    assert "FAILED cell" in capsys.readouterr().err
