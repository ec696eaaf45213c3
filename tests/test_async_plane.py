"""Buffered-async round plane tests (repro.fl.async_plane).

Pins the PR-9 contracts:

* **Degeneracy bit-identity** — async with K = everything, zero delays and
  the discount off reproduces the sync host executor exactly (params AND
  Eq.-15 ledger) for fedavg and feddif at N = 20.
* **Staleness-weight normalization** — discounted weights renormalize to 1
  inside the Eq.-11 mean (plain numpy sweeps, no hypothesis).
* **Event-queue determinism** — same seed ⇒ identical event order (virtual
  clock, arrival counts, staleness, curves) across runs and across
  ``--resume``.
* **Kill/resume** — the mid-tick pending buffer rides the commit-marker
  protocol: a preempted buffered run resumes bit-identically.
* **Hop parking** — a hop deadline parks late diffusion hops (training
  skipped) while their wire events stay charged.
* **Population sampling** — deterministic availability-weighted cohorts.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.fl.engine import AsyncSpec, EngineSpec
from repro.fl.experiment import ExperimentSpec, run_experiment
from repro.fl.resume import Preempted, RoundCheckpointer
from repro.fl.server import FLConfig


def _spec(strategy="fedavg", n=4, rounds=2, engine="host", **fl_kw):
    return ExperimentSpec(
        task="fcn", alpha=0.5, num_samples=600,
        fl=FLConfig(strategy=strategy, rounds=rounds, num_clients=n,
                    num_models=n, seed=0, topology_seed=0, eval_every=1,
                    engine=engine, **fl_kw))


def _trees_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


_DEGENERATE = EngineSpec(mode="async", data_plane="host")


# ------------------------------------------------------ degeneracy contract

@pytest.mark.parametrize("strategy", ["fedavg", "feddif"])
def test_degenerate_async_bit_identical_to_host_n20(strategy):
    """K = all, zero delays, discount off, host inner plane ⇒ the async
    event queue replays the sync host executor bit for bit at N = 20."""
    host = run_experiment(_spec(strategy, n=20))
    async_ = run_experiment(_spec(strategy, n=20, engine=_DEGENERATE))
    assert _trees_equal(host.params, async_.params)
    assert host.ledger.as_dict() == async_.ledger.as_dict()
    assert host.accuracy == async_.accuracy
    assert host.history.diffusion_rounds == async_.history.diffusion_rounds
    # degenerate ticks: one per round, everything arrives at t=0, fresh
    assert async_.history.virtual_s == [0.0, 0.0]
    assert all(s == 0.0 for s in async_.history.staleness)


def test_degenerate_async_matches_under_churn():
    """apply_round_churn is shared: the masked schedule degenerates too."""
    host = run_experiment(_spec("fedavg", n=6, churn_rate=0.3))
    async_ = run_experiment(
        _spec("fedavg", n=6, churn_rate=0.3, engine=_DEGENERATE))
    assert _trees_equal(host.params, async_.params)
    assert host.ledger.as_dict() == async_.ledger.as_dict()


def test_async_rejects_persistent_and_delta_strategies():
    for strategy in ("gossip", "stc"):
        with pytest.raises(ValueError, match="buffered-async"):
            run_experiment(_spec(strategy, engine=_DEGENERATE))


# ------------------------------------------------ staleness normalization

def test_discounted_weights_renormalize_to_one():
    """Eq.-11 aggregation of constant trees is exactly that constant no
    matter how weights are discounted — numpy sweep over staleness mixes."""
    from repro.fl.async_plane import _Contribution, _discounted_fedavg

    rng = np.random.default_rng(0)
    b = AsyncSpec(staleness_alpha=0.7, staleness_beta=1.3)
    for trial in range(25):
        k = int(rng.integers(1, 9))
        popped = [
            _Contribution(arrival_s=float(rng.random()), seq=i,
                          round=int(rng.integers(0, 5)), slot=i,
                          weight=float(rng.uniform(0.1, 10.0)),
                          tree={"w": np.full((3,), 7.5, np.float32)})
            for i in range(k)]
        tick = 6
        out, stale = _discounted_fedavg(popped, tick, b)
        np.testing.assert_allclose(np.asarray(out["w"]), 7.5, rtol=1e-6)
        assert stale == np.mean([tick - c.round for c in popped])
        # the discounted weights themselves normalize to 1
        w = np.array([c.weight * b.discount(tick - c.round) for c in popped],
                     np.float64)
        np.testing.assert_allclose((w / w.sum()).sum(), 1.0, rtol=1e-12)


def test_zero_weight_tick_leaves_global_unchanged():
    """Empty Dirichlet shards arrive instantly; a tick popping only
    zero-weight contributions must be a no-op, not a ValueError."""
    from repro.fl.async_plane import _Contribution, _discounted_fedavg

    popped = [_Contribution(arrival_s=0.0, seq=i, round=0, slot=i,
                            weight=0.0, tree={"w": np.ones(2, np.float32)})
              for i in range(3)]
    out, stale = _discounted_fedavg(popped, 1, AsyncSpec())
    assert out is None
    assert stale == 1.0


def test_zero_staleness_discount_is_exactly_unity():
    b = AsyncSpec(staleness_alpha=1.0, staleness_beta=0.9)
    # weight * discount(0) must be bitwise w * 1.0 — the degeneracy proof
    for w in np.random.default_rng(1).uniform(0.01, 100.0, 50):
        assert w * b.discount(0) == w


# ------------------------------------------------- event-queue determinism

def test_event_queue_deterministic_across_runs():
    spec = _spec("fedavg", n=6, rounds=3, engine="async", churn_rate=0.05)
    r1 = run_experiment(spec)
    r2 = run_experiment(spec)
    assert r1.history.virtual_s == r2.history.virtual_s
    assert r1.history.arrivals == r2.history.arrivals
    assert r1.history.staleness == r2.history.staleness
    assert r1.accuracy == r2.accuracy
    assert _trees_equal(r1.params, r2.params)


def test_buffered_async_diverges_from_barrier_but_charges_same_ledger():
    """The two preset arms replay identical schedules — identical Eq.-15
    ledgers — while the buffered arm's virtual clock runs ahead."""
    r_barrier = run_experiment(_spec("fedavg", n=6, rounds=3,
                                     engine="async_barrier"))
    r_async = run_experiment(_spec("fedavg", n=6, rounds=3, engine="async"))
    assert r_barrier.ledger.as_dict() == r_async.ledger.as_dict()
    # barrier ticks advance to the slowest arrival; buffered to the K-th
    assert (r_async.history.virtual_s[0]
            < r_barrier.history.virtual_s[0])
    assert max(r_barrier.history.staleness) == 0.0
    assert max(r_async.history.staleness) > 0.0


# ----------------------------------------------------------- kill / resume

def test_async_kill_resume_bit_identical_with_pending_buffer(
        tmp_path, monkeypatch):
    """Preempt mid-run with buffer_k < N (contributions pending in the
    heap at the checkpoint boundary); the resumed run must be bitwise the
    clean run — params, ledger, virtual clock, curves."""
    eng = EngineSpec(mode="async", buffered=AsyncSpec(
        buffer_k=2, staleness_beta=0.5, delay_scale=0.01, delay_sigma=1.0))

    def mkspec():
        return _spec("fedavg", n=4, rounds=4, engine=eng,
                     checkpoint_every=1)

    clean = run_experiment(mkspec(), checkpoint_dir=str(tmp_path / "clean"))
    killed_dir = str(tmp_path / "killed")
    monkeypatch.setattr(RoundCheckpointer, "fail_after_save", 2)
    with pytest.raises(Preempted):
        run_experiment(mkspec(), checkpoint_dir=killed_dir)
    monkeypatch.setattr(RoundCheckpointer, "fail_after_save", None)
    resumed = run_experiment(mkspec(), checkpoint_dir=killed_dir)
    assert _trees_equal(clean.params, resumed.params)
    assert clean.ledger.as_dict() == resumed.ledger.as_dict()
    assert clean.history.virtual_s == resumed.history.virtual_s
    assert clean.history.arrivals == resumed.history.arrivals
    assert clean.accuracy == resumed.accuracy


def test_resume_refuses_changed_engine(tmp_path):
    """The engine fingerprint joins the checkpoint config guard: resuming
    an async run under different async knobs must be refused."""
    eng = EngineSpec(mode="async", buffered=AsyncSpec(buffer_k=2))
    spec = _spec("fedavg", n=4, rounds=4, engine=eng, checkpoint_every=1)
    d = str(tmp_path / "ck")
    monkey = RoundCheckpointer.fail_after_save
    RoundCheckpointer.fail_after_save = 2
    try:
        with pytest.raises(Preempted):
            run_experiment(spec, checkpoint_dir=d)
    finally:
        RoundCheckpointer.fail_after_save = monkey
    other = dataclasses.replace(
        spec, fl=dataclasses.replace(
            spec.fl, engine=EngineSpec(
                mode="async", buffered=AsyncSpec(buffer_k=3))))
    with pytest.raises(ValueError, match="different config"):
        run_experiment(other, checkpoint_dir=d)


# ------------------------------------------------------------- hop parking

def test_hop_deadline_parks_hops_but_charges_full_wire():
    """A tiny hop deadline parks (almost) every diffusion hop's training
    session, yet the wire events stay charged — the ledgers of the parked
    and unparked runs are identical (Eq. 15: stale airtime is airtime)."""
    base = EngineSpec(mode="async", data_plane="host", buffered=AsyncSpec(
        delay_scale=0.01, delay_sigma=0.5))
    tight = dataclasses.replace(base, buffered=dataclasses.replace(
        base.buffered, hop_deadline_s=1e-9))
    free = run_experiment(_spec("d2d_random_walk", n=6, rounds=2,
                                engine=base))
    parked = run_experiment(_spec("d2d_random_walk", n=6, rounds=2,
                                  engine=tight))
    assert sum(free.history.parked_hops) == 0
    assert sum(parked.history.parked_hops) > 0
    assert free.ledger.as_dict() == parked.ledger.as_dict()
    assert not _trees_equal(free.params, parked.params)


# ------------------------------------------------------------- population

def test_population_cohorts_are_deterministic_and_availability_weighted():
    from repro.fl.population import Population

    pop = Population(size=500, num_shards=10, seed=3)
    a = pop.sample_cohort(t=7, k=20)
    b = pop.sample_cohort(t=7, k=20)
    assert np.array_equal(a.users, b.users)
    assert len(set(a.users.tolist())) == 20          # without replacement
    assert np.array_equal(a.shards, pop.shard_of(a.users))
    assert a.shards.max() < 10 and a.users.max() < 500
    # different ticks draw different cohorts
    c = pop.sample_cohort(t=8, k=20)
    assert not np.array_equal(a.users, c.users)
    # Efraimidis–Spirakis: high-availability users appear more often
    counts = np.zeros(500)
    for t in range(300):
        counts[pop.sample_cohort(t=t, k=20).users] += 1
    hi = pop.availability > np.quantile(pop.availability, 0.8)
    lo = pop.availability < np.quantile(pop.availability, 0.2)
    assert counts[hi].mean() > 2.0 * counts[lo].mean()


def test_population_cohort_run_is_deterministic():
    eng = EngineSpec(mode="async", buffered=AsyncSpec(
        buffer_frac=0.5, delay_scale=0.01, delay_sigma=1.0,
        population=200))
    r1 = run_experiment(_spec("fedavg", n=4, rounds=2, engine=eng))
    r2 = run_experiment(_spec("fedavg", n=4, rounds=2, engine=eng))
    assert _trees_equal(r1.params, r2.params)
    assert r1.accuracy == r2.accuracy
    assert r1.history.virtual_s == r2.history.virtual_s
