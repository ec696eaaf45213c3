"""RoundSchedule / Executor seam: host and fleet data planes must agree.

The schedule is computed once per round from the control plane, so the
ledger totals are *identical* by construction (both executors replay the
same wire events); the trained parameters must agree to vmap-vs-loop float
tolerance.  Every Table-II strategy must run end-to-end on both executors.
A fleet session computed on a compact stack of its training slots must
train them as the full-width session does.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.schedule import (MixOp, PermuteOp, RoundSchedule,
                                 WireEvent, charge_schedule,
                                 complete_round_permutation)
from repro.channels.resources import ResourceLedger
from repro.fl import ExperimentSpec, FLConfig, run_experiment
from repro.fl.adapters import FrozenLoss
from repro.fl.executors import (FleetExecutor, ShardedFleetExecutor,
                                compact_slots, session_widths)
from repro.fl.server import STRATEGIES


def _spec(strategy, executor, rounds=2, clients=5, **kw):
    return ExperimentSpec(
        task="fcn", alpha=0.3, num_samples=1200,
        fl=FLConfig(strategy=strategy, rounds=rounds, num_clients=clients,
                    num_models=clients, seed=0, topology_seed=3,
                    executor=executor, tthf_cluster_size=2,
                    tthf_global_period=2, **kw))


# ------------------------------------------------------------------ schedule

def test_complete_round_permutation_bijects_and_parks():
    # 3 slots; model 0 at slot 0 hops to slot 1 (occupied by model 1).
    src_of_dst, mask, slots = complete_round_permutation(
        [(0, 1)], np.array([0, 1, 2]), 3)
    assert sorted(src_of_dst.tolist()) == [0, 1, 2]
    assert mask.tolist() == [False, True, False]
    assert slots[0] == 1                      # scheduled hop
    assert sorted(slots.tolist()) == [0, 1, 2]  # one model per slot


def test_charge_schedule_replays_every_event_kind():
    led = ResourceLedger()
    sched = RoundSchedule(
        num_slots=2, ops=[],
        wire=[WireEvent("downlink", 1e6, 2.0, 2),
              WireEvent("d2d", 1e6, 1.0),
              WireEvent("uplink", 5e5, 2.0)],
        agg=[(0, 1.0), (1, 1.0)])
    charge_schedule(led, sched)
    assert led.downlink_models == 1
    assert led.uplink_models == 1
    assert led.transmitted_models == 2        # d2d + uplink
    assert led.subframes > 0
    with pytest.raises(ValueError):
        charge_schedule(led, dataclasses.replace(
            sched, wire=[WireEvent("sideways", 1.0, 1.0)]))


def test_mixop_matrix_is_row_stochastic():
    op = MixOp((((0, 2), (3.0, 1.0)),))
    w = op.matrix(4)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_allclose(w[0], [0.75, 0.0, 0.25, 0.0])
    np.testing.assert_allclose(w[1], [0.0, 1.0, 0.0, 0.0])


def test_permuteop_compress_src_mask():
    op = PermuteOp(np.array([2, 0, 1]), np.array([True, False, True]),
                   compress=True)
    # trained dsts 0 and 2 receive from slots 2 and 1.
    assert op.compress_src_mask().tolist() == [False, True, True]


# ----------------------------------------------------- host vs fleet parity

@pytest.mark.parametrize("strategy", ["feddif", "fedavg", "fedswap"])
def test_host_fleet_parity(strategy):
    """Same seed + config: final params allclose, ledgers identical."""
    host = run_experiment(_spec(strategy, "host"))
    fleet = run_experiment(_spec(strategy, "fleet"))
    assert host.ledger.as_dict() == fleet.ledger.as_dict()
    assert host.diffusion_rounds == fleet.diffusion_rounds
    np.testing.assert_allclose(host.iid_distance, fleet.iid_distance,
                               atol=1e-6)
    for a, b in zip(jax.tree.leaves(host.final_params),
                    jax.tree.leaves(fleet.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(host.accuracy, fleet.accuracy, atol=0.05)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_every_strategy_runs_on_fleet_executor(strategy):
    """All 10 Table-II strategies execute on the client-stacked data plane."""
    res = run_experiment(_spec(strategy, "fleet", rounds=1, clients=4))
    assert len(res.accuracy) == 1
    assert 0.0 <= res.accuracy[0] <= 1.0
    assert np.all(np.isfinite(
        np.concatenate([np.asarray(x, np.float32).ravel()
                        for x in jax.tree.leaves(res.final_params)])))


def test_fleet_rejects_unknown_executor():
    with pytest.raises(AssertionError):
        run_experiment(_spec("fedavg", "warp"))


def test_rejects_more_models_than_clients():
    """M ≤ N (constraint 18d): a clear error, not a slot-invariant crash."""
    spec = _spec("feddif", "host", clients=4)
    spec = dataclasses.replace(
        spec, fl=dataclasses.replace(spec.fl, num_models=8))
    with pytest.raises(ValueError, match="num_models"):
        run_experiment(spec)


# ------------------------------------------------- compact fleet sessions
DIM, CLASSES, ROWS = 6, 3, 4


def _logits_loss(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _plain_loss(p, batch):
    return _logits_loss(batch["x"] @ p["w"] + p["b"], batch["y"])


def _frozen_loss():
    def fn(p, batch, frozen):
        h = jnp.tanh(batch["x"] @ frozen["proj"])
        return (_logits_loss(h @ p["w"] + p["b"], batch["y"]),
                {"fl.test.rows": jnp.int32(batch["y"].shape[0])})
    proj = jax.random.normal(jax.random.PRNGKey(9), (DIM, DIM))
    return FrozenLoss(fn, {"proj": proj})


def _client_batches(num_slots):
    """Ragged epochs (1-3 batches a client), the same on every draw."""
    rng = np.random.default_rng(5)
    epochs = [[{"x": rng.normal(size=(ROWS, DIM)).astype(np.float32),
                "y": rng.integers(0, CLASSES, ROWS).astype(np.int32)}
               for _ in range(1 + c % 3)] for c in range(num_slots)]
    return [lambda e=e: list(e) for e in epochs]


def _fleet(kind, num_slots, cls=FleetExecutor):
    cfg = FLConfig(strategy="feddif_prox" if kind == "prox" else "feddif",
                   num_clients=num_slots, num_models=num_slots, lr=0.3,
                   momentum=0.9, prox_mu=0.5)
    loss = _frozen_loss() if kind == "frozen" else _plain_loss
    return cls(loss, _client_batches(num_slots), cfg)


def _stack(num_slots, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w": jax.random.normal(k1, (num_slots, DIM, CLASSES)),
            "b": jax.random.normal(k2, (num_slots, CLASSES))}


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _mask(num_slots, k, seed=0):
    mask = np.zeros(num_slots, bool)
    mask[np.random.default_rng(seed).choice(num_slots, k,
                                            replace=False)] = True
    return mask


@pytest.mark.parametrize("slots,widths", [(16, (4, 8, 12, 16)),
                                          (256, (64, 128, 192, 256)),
                                          (5, (2, 3, 4, 5)), (1, (1,))])
def test_session_widths_are_the_quarters_of_the_fleet(slots, widths):
    assert session_widths(slots) == widths


@pytest.mark.parametrize("k,width", [(1, 4), (4, 4), (5, 8), (8, 8),
                                     (12, 12), (13, 16), (16, 16)])
def test_compact_slots_take_the_smallest_quarter_that_holds_the_mask(
        k, width):
    mask = _mask(16, k, seed=k)
    idx = compact_slots(mask)
    assert len(idx) == width
    assert idx[:k].tolist() == np.flatnonzero(mask).tolist()
    assert idx[k:].tolist() == np.flatnonzero(~mask)[:width - k].tolist()
    assert len(set(idx.tolist())) == width


@pytest.mark.parametrize("kind", ["plain", "prox", "frozen"])
@pytest.mark.parametrize("k,width", [(1, 4), (4, 4), (5, 8), (8, 8),
                                     (12, 12), (16, 16)])
def test_a_compact_session_trains_as_the_full_width_one(kind, k, width):
    """The trained slots get the full-width session's params, the idle
    ones keep theirs bit for bit, and the session computes ``width`` slots
    a step (the smallest quarter of 16 that holds k)."""
    c = 16
    mask = _mask(c, k, seed=k)
    params = _stack(c)
    ex, ref = _fleet(kind, c), _fleet(kind, c)
    obs.snapshot()
    with obs.recording():
        got = ex._session(_copy(params), mask)
        rec = obs.snapshot()
    want = ref._full_session(_copy(params), *ref._draw_session(mask))
    steps = max(1 + i % 3 for i in np.flatnonzero(mask))
    assert rec.counters["fl.exec.steps"] == steps
    assert rec.counters["fl.exec.slot_steps"] == c * steps
    assert rec.counters["fl.exec.computed_slot_steps"] == width * steps
    assert rec.counters["fl.exec.compacted_sessions"] == int(width < c)
    for g, w, p in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(params)):
        g, w, p = np.asarray(g), np.asarray(w), np.asarray(p)
        np.testing.assert_allclose(g[mask], w[mask], rtol=1e-5, atol=1e-6)
        assert not np.allclose(g[mask], p[mask])
        np.testing.assert_array_equal(g[~mask], p[~mask])


@pytest.mark.parametrize("kind", ["plain", "prox", "frozen"])
def test_the_first_session_compiles_every_width_and_later_ones_nothing(
        kind):
    """The first session (full width, through ``_step``) compiles the
    compact programs of every narrower width; a recorded run of sessions
    at every width then compiles nothing, and only full-width sessions go
    through ``_step``."""
    c = 16
    ex = _fleet(kind, c)
    widths, step = [], ex._step

    def counted(*args):
        widths.append(jax.tree.leaves(args[0])[0].shape[0])
        return step(*args)
    ex._step = counted
    params = ex._session(_stack(c), np.ones(c, bool))
    assert widths == [c] * 3
    (programs,) = ex._compact.values()
    assert sorted(programs) == [4, 8, 12]
    obs.snapshot()
    with obs.recording():
        for seed, k in enumerate((1, 5, 9, 13, 16, 3)):
            params = ex._session(params, _mask(c, k, seed))
        rec = obs.snapshot()
    assert rec.compiles == {}
    assert rec.counters["fl.exec.compacted_sessions"] == 4
    assert widths[3:] == [c] * (len(widths) - 3) and len(widths) > 3


def test_every_width_replays_one_trace_of_the_loss():
    """The first session builds the step at every width, and the loss's
    Python runs once for all of them: each width batches one jaxpr."""
    calls = []

    def loss(p, batch):
        calls.append(1)
        return _plain_loss(p, batch)
    c = 16
    ex = FleetExecutor(loss, _client_batches(c), _fleet("plain", c).cfg)
    ex._session(_stack(c), np.ones(c, bool))
    (programs,) = ex._compact.values()
    assert sorted(programs) == [4, 8, 12]
    assert len(calls) == 1


def test_the_sharded_engine_computes_every_slot():
    """The sharded engine keeps the full-width session: a compact subset of
    its slots would not be split over the mesh."""
    c = 8
    ex = _fleet("plain", c, ShardedFleetExecutor)
    ref = _fleet("plain", c)
    params = _stack(c)
    mask = _mask(c, 2)
    obs.snapshot()
    with obs.recording():
        got = ex._session(ex.adopt_slots(jax.device_get(params)), mask)
        rec = obs.snapshot()
    want = ref._session(_copy(params), mask)
    steps = max(1 + i % 3 for i in np.flatnonzero(mask))
    assert rec.counters["fl.exec.computed_slot_steps"] == ex.c_pad * steps
    assert "fl.exec.compacted_sessions" not in rec.counters
    assert ex._compact == {}
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g)[:c], np.asarray(w),
                                   rtol=1e-5, atol=1e-6)
