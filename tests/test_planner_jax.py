"""Device-resident planner plane: host-vs-jax parity + building blocks.

The acceptance bar for the jax control plane is *identical hop lists*
(model, src, dst, round) to the host numpy oracle on the default feddif
config, plus bit-identical ledger charges end-to-end.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channels.fading import ChannelModel
from repro.channels.resources import (outage_probability,
                                      outage_probability_jax,
                                      required_bandwidth,
                                      required_bandwidth_jax,
                                      spectral_efficiency,
                                      spectral_efficiency_jax)
from repro.channels.topology import CellTopology
from repro.core import DiffusionPlanner, DiffusionState, PlannerState
from repro.core.diffusion import DiffusionHop, DiffusionPlan, PlanCache
from repro.fl.engine import EngineSpec


def _mkstate(n, m, c, dsi, sizes):
    state = DiffusionState.init(m, n, c)
    for mi in range(m):
        state.record_training(mi, mi % n, dsi[mi % n], float(sizes[mi % n]))
    return state


def _hoplist(plan):
    return [(h.model, h.src, h.dst, h.round_index) for h in plan.hops]


@pytest.mark.parametrize("seed", range(3))
def test_host_vs_jax_planner_parity_default_config(seed):
    """Default feddif planner knobs (ε=0.04, γ_min=1, N=M=10): both modes
    must emit identical hop lists and identical post-plan states."""
    n = m = c = 10
    rng = np.random.default_rng(seed)
    dsi = rng.dirichlet(np.ones(c) * 0.5, n).astype(np.float32)
    sizes = rng.integers(200, 800, n).astype(np.float64)
    pos = CellTopology().sample_positions(np.random.default_rng(seed + 50), n)

    st_h = _mkstate(n, m, c, dsi, sizes)
    plan_h = DiffusionPlanner().plan_communication_round(
        st_h, dsi, sizes, np.random.default_rng(seed + 7), positions=pos)

    st_j = _mkstate(n, m, c, dsi, sizes)
    plan_j = DiffusionPlanner(mode="jax").plan_communication_round(
        st_j, dsi, sizes, np.random.default_rng(seed + 7), positions=pos)

    assert plan_h.num_rounds == plan_j.num_rounds
    assert _hoplist(plan_h) == _hoplist(plan_j)
    assert plan_h.num_rounds > 0          # a real plan, not a vacuous pass
    for hh, hj in zip(plan_h.hops, plan_j.hops):
        assert hj.gamma == pytest.approx(hh.gamma, rel=0, abs=0)
        assert hj.bandwidth == pytest.approx(hh.bandwidth, rel=0, abs=0)
    np.testing.assert_array_equal(st_h.holder, st_j.holder)
    np.testing.assert_array_equal(st_h.visited, st_j.visited)
    # XLA fuses the Eq.-2 chain inside the jitted loop, so the DoLs may
    # drift by float32 ulps; the *decisions* above must still coincide.
    np.testing.assert_allclose(st_h.dol, st_j.dol, rtol=3e-5, atol=1e-7)
    assert st_h.round_index == st_j.round_index


def test_host_vs_jax_end_to_end_ledger_parity():
    """Full feddif experiment, planner='host' vs 'jax': same accuracy curve
    and a bit-identical ResourceLedger (schedules coincide hop for hop)."""
    from repro.fl.experiment import ExperimentSpec, run_experiment
    from repro.fl.server import FLConfig
    spec = ExperimentSpec(
        task="fcn", alpha=0.5, num_samples=400,
        fl=FLConfig(strategy="feddif", rounds=2, num_clients=4, num_models=4,
                    seed=0, topology_seed=3, max_diffusion_rounds=8))
    r_host = run_experiment(spec)
    spec_j = dataclasses.replace(
        spec, fl=dataclasses.replace(spec.fl,
                                     engine=EngineSpec(planner="jax")))
    r_jax = run_experiment(spec_j)
    assert r_host.ledger.as_dict() == r_jax.ledger.as_dict()
    assert r_host.accuracy == r_jax.accuracy
    assert r_host.diffusion_rounds == r_jax.diffusion_rounds


def test_batched_preplan_matches_per_round_plans():
    """prepopulate_plan_cache must store plans the per-round jax (and host)
    path reproduces: a sweep run with a pre-populated cache sees zero
    misses and charges the same ledger as an uncached host run."""
    from repro.experiments import run_sweep
    art = run_sweep("fig5_gamma_min", smoke=True, seeds=(0,), out_dir=None,
                    engine=EngineSpec(planner="jax"), num_samples=300)
    assert art["planner"] == "jax"
    assert art["plan_cache"]["misses"] == 0
    assert art["plan_cache"]["hits"] > 0
    host = run_sweep("fig5_gamma_min", smoke=True, seeds=(0,), out_dir=None,
                     engine=EngineSpec(planner="host"), num_samples=300)
    for cj, ch in zip(art["cells"], host["cells"]):
        assert cj["comm"] == ch["comm"]
        assert cj["accuracy"] == ch["accuracy"]


def test_channel_jax_twins_match_numpy():
    rng = np.random.default_rng(0)
    topo, chan = CellTopology(), ChannelModel()
    pos = topo.sample_positions(rng, 8)
    dist = topo.pairwise_distances(pos)
    np.testing.assert_allclose(np.asarray(topo.pairwise_distances_jax(pos)),
                               dist, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(chan.large_scale_db_jax(dist)),
                               chan.large_scale_db(dist), rtol=1e-5)
    gains = chan.sample_gains(dist, rng)
    np.testing.assert_allclose(np.asarray(chan.snr_jax(gains)),
                               chan.snr(gains), rtol=1e-5)
    snr = chan.snr(gains)
    np.testing.assert_allclose(np.asarray(spectral_efficiency_jax(snr)),
                               spectral_efficiency(snr), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(required_bandwidth_jax(1e6, spectral_efficiency(snr))),
        required_bandwidth(1e6, spectral_efficiency(snr)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outage_probability_jax(1.0, snr)),
                               outage_probability(1.0, snr),
                               rtol=1e-5, atol=1e-9)
    # device-keyed draws: right shape/positivity, deterministic per key
    key = jax.random.PRNGKey(0)
    g1 = chan.sample_gains_jax(key, jnp.asarray(dist))
    g2 = chan.sample_gains_jax(key, jnp.asarray(dist))
    assert g1.shape == dist.shape and bool(jnp.all(g1 > 0))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    p1 = topo.sample_positions_jax(key, 8)
    assert p1.shape == (8, 2)
    assert bool(jnp.all(jnp.linalg.norm(p1, axis=-1) <= topo.radius_m + 1e-3))


@pytest.mark.parametrize("n,m,c", [(5, 4, 6), (256, 256, 10)])
def test_planner_state_matches_mutable_state(n, m, c):
    """PlannerState.record_training / record_round mirror the mutable
    DiffusionState bookkeeping bit for bit, at a toy size and at the
    benchmark's FedDif size (N=M=256, C=10)."""
    rng = np.random.default_rng(1)
    dsi = rng.dirichlet(np.ones(c), n).astype(np.float32)
    sizes = rng.integers(50, 200, n).astype(np.float64)
    host = DiffusionState.init(m, n, c)
    fstate = PlannerState.init(m, n, c)
    for mi in range(m):
        host.record_training(mi, mi % n, dsi[mi % n], float(sizes[mi % n]))
        fstate = fstate.record_training(mi, mi % n, dsi[mi % n],
                                        float(sizes[mi % n]))
    np.testing.assert_allclose(np.asarray(fstate.dol), host.dol, atol=0)
    np.testing.assert_allclose(np.asarray(fstate.chain_size),
                               host.chain_size, atol=0)
    np.testing.assert_array_equal(np.asarray(fstate.holder), host.holder)
    # one masked round from non-zero chains: distinct destinations, about
    # half the models hop
    dst = rng.permutation(n)[:m]
    mask = rng.random(m) < 0.5
    mask[0] = True
    fstate2 = fstate.record_round(jnp.asarray(dst), jnp.asarray(mask),
                                  jnp.asarray(dsi), jnp.asarray(sizes))
    for mi in range(m):
        if mask[mi]:
            host.record_training(mi, int(dst[mi]), dsi[dst[mi]],
                                 float(sizes[dst[mi]]))
    np.testing.assert_allclose(np.asarray(fstate2.dol), host.dol, atol=0)
    np.testing.assert_allclose(np.asarray(fstate2.chain_size),
                               host.chain_size, atol=0)
    np.testing.assert_array_equal(np.asarray(fstate2.visited), host.visited)
    np.testing.assert_array_equal(np.asarray(fstate2.holder), host.holder)
    # functional() / update_from round-trip
    host2 = DiffusionState.init(m, n, c)
    host2.update_from(fstate2, rounds_advanced=1)
    np.testing.assert_allclose(host2.dol, host.dol, atol=0)
    assert host2.round_index == 1


def test_diffusion_state_initial_training_stays_on_host():
    """FedDif's initial-training stage (``DiffusionState.init`` and one
    Eq.-(2) fold a model, as ``schedule_feddif`` runs it) at the benchmark's
    size moves nothing to or from the device, and keeps its fields NumPy
    float32."""
    n = m = 256
    c = 10
    rng = np.random.default_rng(2)
    dsi = rng.dirichlet(np.ones(c), n).astype(np.float32)
    sizes = rng.integers(50, 200, n).astype(np.float64)
    with jax.transfer_guard("disallow"):
        state = DiffusionState.init(m, n, c)
        for mi in range(m):
            holder = int(state.holder[mi])
            state.record_training(mi, holder, dsi[holder],
                                  float(sizes[holder]))
    for field in (state.dol, state.chain_size):
        assert type(field) is np.ndarray
        assert field.dtype == np.float32
    # one fold from an empty chain adopts the client's DSI and data size
    np.testing.assert_array_equal(state.chain_size,
                                  sizes[state.holder].astype(np.float32))
    np.testing.assert_allclose(state.dol, dsi[state.holder], rtol=1e-6)
    assert state.visited[np.arange(m), state.holder].all()


def test_as_permutations_keeps_never_hopping_models():
    """Satellite fix: M must come from the planner, not max(h.model)+1 —
    otherwise models that never hop vanish from slot bookkeeping."""
    hop = DiffusionHop(model=0, src=0, dst=2, gamma=1.0, bandwidth=1.0,
                       decrement=0.1, round_index=0)
    plan = DiffusionPlan(hops=[hop], num_rounds=1,
                         final_iid_distance=np.zeros(3),
                         efficiency_per_round=[0.1], num_models=3)
    assert plan.num_models == 3
    perms = plan.as_permutations(3)
    assert len(perms) == 1
    perm, mask = perms[0]
    assert sorted(perm.tolist()) == [0, 1, 2]
    assert mask.tolist() == [False, False, True]
    # explicit override beats the stored value
    perms2 = plan.as_permutations(3, num_models=3)
    assert perms2[0][0].tolist() == perm.tolist()
    # a plan produced by the planner records M even when some models idle
    rng = np.random.default_rng(0)
    n, m, c = 6, 3, 5
    dsi = rng.dirichlet(np.ones(c), n).astype(np.float32)
    sizes = rng.integers(100, 300, n).astype(np.float64)
    state = _mkstate(n, m, c, dsi, sizes)
    p = DiffusionPlanner(epsilon=0.04, max_rounds=4).plan_communication_round(
        state, dsi, sizes, rng)
    assert p.num_models == m


def test_jax_planner_cache_roundtrip():
    """jax plans store/replay through PlanCache like host plans do."""
    n = m = c = 6
    rng = np.random.default_rng(2)
    dsi = rng.dirichlet(np.ones(c), n).astype(np.float32)
    sizes = rng.integers(100, 400, n).astype(np.float64)
    pos = CellTopology().sample_positions(np.random.default_rng(9), n)
    cache = PlanCache()
    key = ("k", 0)
    planner = DiffusionPlanner(mode="jax", max_rounds=8)
    st1 = _mkstate(n, m, c, dsi, sizes)
    plan1 = planner.plan_communication_round(
        st1, dsi, sizes, np.random.default_rng(3), positions=pos,
        cache=cache, cache_key=key)
    st2 = _mkstate(n, m, c, dsi, sizes)
    plan2 = planner.plan_communication_round(
        st2, dsi, sizes, np.random.default_rng(99), positions=pos,
        cache=cache, cache_key=key)        # different rng: must be a replay
    assert cache.hits == 1
    assert _hoplist(plan1) == _hoplist(plan2)
    np.testing.assert_array_equal(st1.holder, st2.holder)
    assert key in cache                    # __contains__ probe, no miss count
    assert cache.stats()["misses"] == 1
