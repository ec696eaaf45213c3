"""``repro.obs``: spans and counters that cost nothing while off, nest and
carry the round while on, land on the profiler's host plane, and follow
the FL round's schedule."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.schedule import MixOp, PermuteOp, TrainOp
from repro.fl.executors import compact_slots


@pytest.fixture(autouse=True)
def _empty():
    obs.snapshot()
    yield
    obs.snapshot()


def test_off_reads_no_clock_and_builds_no_annotation(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("touched while off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(obs.time, "perf_counter_ns", boom)
    assert not obs.enabled()
    with obs.span("fl.a"):
        with obs.span("fl.b"):
            obs.count("fl.n", 3)
    assert obs.span("fl.a") is obs.span("fl.c")      # one shared no-op
    rec = obs.snapshot()
    assert rec.spans == [] and rec.counters == {} and rec.compiles == {}


def test_timed_stamps_with_recording_off():
    with obs.timed("fl.exec") as s:
        pass
    assert s.seconds >= 0.0
    assert obs.snapshot().spans == []


def test_spans_nest_carry_parent_round_and_self_time():
    with obs.recording():
        with obs.fl_round(7):
            with obs.span("fl.plan"):
                with obs.span("fl.plan.auction"):
                    obs.count("fl.plan.hops", 2)
                with obs.span("fl.plan.build"):
                    pass
            obs.count("fl.plan.hops")
        with obs.span("fl.outside"):
            pass
    rec = obs.snapshot()
    by = {s.name: s for s in rec.spans}
    assert [s.name for s in rec.spans] == [
        obs.ANCHOR, "fl.plan.auction", "fl.plan.build", "fl.plan",
        "fl.round", "fl.outside"]
    assert by["fl.plan.auction"].parent == "fl.plan"
    assert by["fl.plan"].parent == "fl.round"
    assert by["fl.round"].parent is None
    assert {by[k].round for k in ("fl.plan", "fl.plan.build",
                                  "fl.round")} == {7}
    assert by["fl.outside"].round is None
    plan = by["fl.plan"]
    kids = sum(by[k].end_ns - by[k].start_ns
               for k in ("fl.plan.auction", "fl.plan.build"))
    assert plan.self_ns == plan.end_ns - plan.start_ns - kids
    assert rec.counters == {"fl.plan.hops": 3}
    assert rec.totals()["fl.round"][0] == 1
    calls, total, self_ns = rec.totals()["fl.plan"]
    assert (calls, total, self_ns) == (1, plan.end_ns - plan.start_ns,
                                       plan.self_ns)
    assert not obs.enabled()


def test_compiles_counted_by_fun_name_and_span():
    def fresh_fn(x):
        return jnp.sin(x) * 3.0 + 1.0

    x = jnp.arange(5.0)
    with obs.recording():
        with obs.span("fl.exec.train"):
            jax.jit(fresh_fn)(x)
    assert obs.snapshot().compiles == {("jit(fresh_fn)", "fl.exec.train"): 1}
    jax.jit(lambda x: x + 2.0)(x)                   # off: not counted
    assert obs.snapshot().compiles == {}


def test_round_records_while_a_profiler_trace_is_captured(tmp_path):
    with obs.fl_round(0):
        with obs.span("fl.plan"):
            pass
    assert obs.snapshot().spans == []
    with jax.profiler.trace(str(tmp_path)):
        with obs.fl_round(1):
            with obs.span("fl.plan"):
                pass
        assert not obs.enabled()          # only inside the round
    names = [(s.name, s.round) for s in obs.snapshot().spans]
    assert names == [(obs.ANCHOR, 1), ("fl.plan", 1), ("fl.round", 1)]


def _host_events(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    planes = jax.profiler.ProfileData.from_file(path).planes
    out = {}
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("fl.", "obs.")):
                        out.setdefault(ev.name, []).append(
                            int(ev.duration_ns))
    return out


def test_spans_land_on_the_host_plane_with_their_durations(tmp_path):
    x = jnp.ones((64, 64))
    with jax.profiler.trace(str(tmp_path)):
        with obs.recording():
            with obs.fl_round(0):
                with obs.span("fl.exec"):
                    for k in range(3):
                        with obs.span("fl.exec.train"):
                            x = jnp.tanh(x @ x).block_until_ready()
                            time.sleep(0.002 * (k + 1))
    rec = obs.snapshot()
    host = _host_events(str(tmp_path))
    assert len(host[obs.ANCHOR]) == 1
    for name in ("fl.round", "fl.exec", "fl.exec.train"):
        mine = sorted(s.end_ns - s.start_ns for s in rec.spans
                      if s.name == name)
        theirs = sorted(host[name])
        assert len(mine) == len(theirs), name
        for a, b in zip(mine, theirs):
            assert abs(a - b) <= max(0.05 * a, 50_000), (name, a, b)


# ------------------------------------------------------- a fleet FedDif round

def _fleet_feddif(clients):
    from repro.fl import ExperimentSpec, FLConfig
    return ExperimentSpec(
        task="fcn", alpha=0.3, num_samples=900,
        fl=FLConfig(strategy="feddif", rounds=2, num_clients=clients,
                    num_models=clients, seed=0, topology_seed=5,
                    executor="fleet", planner="jax", batch_size=16,
                    gamma_min=0.5, epsilon=0.01))


@pytest.mark.parametrize("clients", [6, 8])
def test_fleet_round_spans_follow_the_schedule(clients, monkeypatch):
    from repro.fl import run_experiment
    from repro.fl.experiment import load_experiment_data
    from repro.fl.schedulers import SCHEDULERS

    spec = _fleet_feddif(clients)
    schedules = []
    plan = SCHEDULERS["feddif"]

    def keep(ctx):
        schedules.append(plan(ctx))
        return schedules[-1]
    monkeypatch.setitem(SCHEDULERS, "feddif", keep)
    with obs.recording():
        run_experiment(spec)
    rec = obs.snapshot()
    _, _, _, loaders = load_experiment_data(spec)
    epoch = np.array([ld.num_batches() for ld in loaders])

    assert rec.totals()["fl.round"][0] == len(schedules) == 2
    for t, sched in enumerate(schedules):
        spans = [s for s in rec.spans if s.round == t]
        names = [s.name for s in spans]
        top = [s.name for s in spans if s.parent == "fl.round"]
        assert top == ["fl.world", "fl.plan", "fl.charge", "fl.exec",
                       "fl.eval"]
        assert [s.name for s in spans if s.parent == "fl.plan"] == [
            "fl.plan.state", "fl.plan.inputs", "fl.plan.auction",
            "fl.plan.decode", "fl.plan.build"]
        trains = [op for op in sched.ops
                  if isinstance(op, (TrainOp, PermuteOp))]
        hops = [op for op in sched.ops if isinstance(op, PermuteOp)]
        assert not any(isinstance(op, MixOp) for op in sched.ops)
        assert names.count("fl.exec.train") == len(trains)
        assert names.count("fl.exec.hop") == len(hops)
        assert names.count("fl.exec.draw") == sum(
            bool(op.train_mask.any()) for op in trains)
        assert names.count("fl.exec.aggregate") == 1
        assert names.count("fl.exec.broadcast") == 1
        for s in spans:
            if s.name.startswith("fl.exec."):
                assert s.parent in ("fl.exec", "fl.exec.train"), s
    trains = [op for sched in schedules for op in sched.ops
              if isinstance(op, (TrainOp, PermuteOp))]
    want = sum(int(epoch[op.train_mask].sum()) for op in trains)
    c = rec.counters
    assert c["fl.exec.active_slot_steps"] == want
    drawn = [op for op in trains if op.train_mask.any()]
    steps = sum(int(epoch[op.train_mask].max()) for op in drawn)
    assert c["fl.exec.steps"] == steps
    assert c["fl.exec.slot_steps"] == clients * steps
    # A session computes the smallest quarter of the fleet that holds its
    # training slots, for every padded step.
    widths = [len(compact_slots(op.train_mask)) for op in drawn]
    assert c["fl.exec.computed_slot_steps"] == sum(
        w * int(epoch[op.train_mask].max()) for w, op in zip(widths, drawn))
    assert c["fl.exec.compacted_sessions"] == sum(w < clients
                                                  for w in widths) > 0
    assert c["fl.exec.h2d_bytes"] > 0
    assert 0 < c["fl.plan.hops"] == sum(
        int(op.train_mask.sum()) for op in trains
        if isinstance(op, PermuteOp))
