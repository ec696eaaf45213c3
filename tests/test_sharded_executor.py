"""Sharded fleet executor: three-plane parity at N=64, permutation-table
routing, and churn/straggler schedule dropout.

Tier-1 runs on one CPU device, where the ``("clients",)`` mesh degenerates
to a single shard — the shard_map program, microbatched sessions and
routing-table permute still execute (collectives become identities).  A
subprocess test forces a 2-device CPU mesh so ppermute / psum_scatter /
psum actually cross shards; CI's smokes job additionally drives the fig7
scaling sweep on a 2-device mesh.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.schedule import (MixOp, PermuteOp, RoundSchedule, TrainOp,
                                 WireEvent, apply_churn)
from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
from repro.fl.executors import _permutation_tables

FUSED = EngineSpec(mode="sharded", shard_overlap="on")


def _spec(strategy, engine, clients=64, rounds=2, **kw):
    # experiment.py trains on the test_frac (0.2) side of the split: 100
    # samples/client keeps every Dirichlet shard non-empty at N=64.
    return ExperimentSpec(
        task="fcn", alpha=0.5, num_samples=100 * clients,
        fl=FLConfig(strategy=strategy, rounds=rounds, num_clients=clients,
                    num_models=clients, seed=0, topology_seed=1,
                    max_diffusion_rounds=4, engine=engine, **kw))


# ------------------------------------------------- three-way parity at N=64

@pytest.mark.parametrize("strategy", ["feddif", "fedavg", "feddif_stc",
                                      "gossip"])
def test_host_fleet_sharded_parity_n64(strategy):
    """Host, fleet and sharded planes at N=64: identical ledgers (bitwise —
    charging is schedule-side), matching final accuracy and params.

    feddif_stc and gossip extend the pair through the kernel data plane
    (``kernels/diffusion.py``): STC-compressed hops exercise ``stc_topk``
    and the gossip MixOp exercises ``mix_aggregate`` on all three planes
    (with ``implementation="auto"`` — the reference twins here, the Pallas
    bodies on TPU / under ``REPRO_KERNELS_IMPL``).  The sharded arm forces
    the fused round plane ("auto" would take op-by-op below
    ``FUSED_MIN_CLIENTS``) so the whole-round program is what parity
    certifies.
    """
    results = {ex: run_experiment(_spec(strategy,
                                        FUSED if ex == "sharded" else ex))
               for ex in ("host", "fleet", "sharded")}
    host = results["host"]
    for ex in ("fleet", "sharded"):
        r = results[ex]
        assert host.ledger.as_dict() == r.ledger.as_dict(), ex
        assert host.diffusion_rounds == r.diffusion_rounds, ex
        np.testing.assert_allclose(host.accuracy, r.accuracy, atol=0.02,
                                   err_msg=ex)
        for a, b in zip(jax.tree.leaves(host.final_params),
                        jax.tree.leaves(r.final_params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-4, rtol=5e-3, err_msg=ex)


def test_sharded_microbatches_session():
    """N=64 over shard_microbatch=16 runs the lax.map chunk path (4 chunks
    per shard on one device) and still matches the un-chunked fleet plane."""
    fleet = run_experiment(_spec("fedavg", "fleet", rounds=1))
    shard = run_experiment(_spec("fedavg", EngineSpec(mode="sharded",
                                                      shard_microbatch=16),
                                 rounds=1))
    assert fleet.ledger.as_dict() == shard.ledger.as_dict()
    for a, b in zip(jax.tree.leaves(fleet.final_params),
                    jax.tree.leaves(shard.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3)


def test_sharded_runs_every_schedule_op_kind():
    """tthf (MixOp), feddif_stc (compressed PermuteOp) and stc (stc_delta
    aggregation) all execute on the sharded plane."""
    for strategy in ("tthf", "feddif_stc", "stc"):
        res = run_experiment(_spec(strategy, FUSED, clients=8, rounds=1,
                                   tthf_cluster_size=4, tthf_global_period=1))
        assert len(res.accuracy) == 1
        assert np.all(np.isfinite(np.concatenate(
            [np.asarray(x, np.float32).ravel()
             for x in jax.tree.leaves(res.final_params)])))


def test_sharded_parity_on_multi_device_mesh():
    """Force a 2-device CPU mesh in a subprocess (XLA_FLAGS is read at jax
    import) so the permute ppermutes and the aggregation psum really cross
    shards; host-vs-sharded must still agree."""
    code = """
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
def spec(executor, **kw):
    return ExperimentSpec(task="fcn", alpha=0.5, num_samples=240,
        fl=FLConfig(strategy="feddif", rounds=1, num_clients=8, num_models=8,
                    seed=0, topology_seed=1, max_diffusion_rounds=3,
                    engine=EngineSpec(mode=executor, **kw)))
host = run_experiment(spec("host"))
shard = run_experiment(spec("sharded", shard_overlap="on"))
assert host.ledger.as_dict() == shard.ledger.as_dict()
for a, b in zip(jax.tree.leaves(host.final_params),
                jax.tree.leaves(shard.final_params)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=5e-4, rtol=5e-3)
print("MULTI_DEVICE_PARITY_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTI_DEVICE_PARITY_OK" in out.stdout


def test_hop_transport_parity_single_device():
    """``shard_hop_transport`` must not change results — "auto" resolves to
    gather for the tiny FCN, so force the ring plane explicitly and compare
    against gather (identical ledgers, matching params)."""
    runs = {t: run_experiment(_spec("feddif", dataclasses.replace(
                                        FUSED, shard_hop_transport=t),
                                    clients=8, rounds=1))
            for t in ("gather", "ring")}
    assert (runs["gather"].ledger.as_dict()
            == runs["ring"].ledger.as_dict())
    for a, b in zip(jax.tree.leaves(runs["gather"].final_params),
                    jax.tree.leaves(runs["ring"].final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3)


def test_ring_transport_parity_on_multi_device_mesh():
    """Forced ring transport on a 2-device mesh: the fused round's
    double-buffered ppermute shifts really cross shards and must still
    match the host reference."""
    code = """
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
def spec(executor, **kw):
    return ExperimentSpec(task="fcn", alpha=0.5, num_samples=240,
        fl=FLConfig(strategy="feddif", rounds=2, num_clients=8, num_models=8,
                    seed=0, topology_seed=1, max_diffusion_rounds=3,
                    engine=EngineSpec(mode=executor, **kw)))
host = run_experiment(spec("host"))
shard = run_experiment(spec("sharded", shard_overlap="on",
                            shard_hop_transport="ring"))
assert host.ledger.as_dict() == shard.ledger.as_dict()
for a, b in zip(jax.tree.leaves(host.final_params),
                jax.tree.leaves(shard.final_params)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=5e-4, rtol=5e-3)
print("RING_TRANSPORT_PARITY_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RING_TRANSPORT_PARITY_OK" in out.stdout


# ------------------------------------------------------- permutation tables

@pytest.mark.parametrize("c,k", [(8, 1), (8, 2), (12, 3), (16, 4)])
def test_permutation_tables_route_every_row(c, k):
    """Replaying the send/recv tables in numpy reproduces take(x, perm)."""
    rng = np.random.default_rng(c * 10 + k)
    for _ in range(5):
        perm = rng.permutation(c)
        send, recv = _permutation_tables(perm, k)
        nl = c // k
        x = np.arange(c)
        out = np.full((k, nl + 1), -1)          # per-shard block + trash row
        for shift in range(k):
            for s in range(k):                  # buffer shipped s -> d
                d = (s + shift) % k
                buf = x[s * nl:(s + 1) * nl][send[s, shift]]
                out[d][recv[d, shift]] = buf
        np.testing.assert_array_equal(out[:, :nl].ravel(), x[perm])


# ----------------------------------------------------------- churn dropout

def _toy_schedule(n=6):
    return RoundSchedule(
        num_slots=n,
        ops=[TrainOp(np.ones(n, dtype=bool)),
             PermuteOp(np.roll(np.arange(n), 1), np.ones(n, dtype=bool)),
             MixOp((((0, 1), (1.0, 1.0)),))],
        wire=[WireEvent("downlink", 1e6, 2.0, n)],
        agg=[(i, float(i + 1)) for i in range(n)])


def test_churned_clients_carry_zero_aggregation_weight():
    drop = np.array([False, True, False, False, True, False])
    out = apply_churn(_toy_schedule(), drop)
    w = out.slot_weights()
    assert w[1] == 0.0 and w[4] == 0.0
    assert (w[[0, 2, 3, 5]] > 0).all()
    # dropped slots train nowhere, in plain and permute ops alike
    assert not out.ops[0].train_mask[[1, 4]].any()
    assert not out.ops[1].train_mask[[1, 4]].any()
    # survivors keep training; the permutation itself is untouched
    assert out.ops[0].train_mask[[0, 2, 3, 5]].all()
    np.testing.assert_array_equal(out.ops[1].src_of_dst,
                                  _toy_schedule().ops[1].src_of_dst)
    # stragglers consumed their airtime: wire events unchanged
    assert out.wire == _toy_schedule().wire


def test_churn_noop_cases():
    sched = _toy_schedule()
    assert apply_churn(sched, np.zeros(6, dtype=bool)) is sched
    # dropping everyone would leave nothing to aggregate -> round unchanged
    assert apply_churn(sched, np.ones(6, dtype=bool)) is sched


def test_churn_rate_runs_end_to_end_and_charges_full_schedule():
    """churn_rate > 0 drops training/weights but never the wire: ledgers of
    churned and unchurned runs of one config are identical."""
    base = run_experiment(_spec("fedavg", "host", clients=8, rounds=2))
    churn = run_experiment(_spec("fedavg", "host", clients=8, rounds=2,
                                 churn_rate=0.4))
    assert base.ledger.as_dict() == churn.ledger.as_dict()
    # with 8 clients at 40% for 2 rounds, some client dropped somewhere:
    # the global models must differ
    diff = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(base.final_params),
                        jax.tree.leaves(churn.final_params)))
    assert diff, "churn at 40% should have dropped at least one client"


def test_churn_parity_across_executors():
    """The churn mask is drawn on the control plane, so every executor
    applies the same dropout."""
    runs = {ex: run_experiment(_spec("feddif", ex, clients=8, rounds=2,
                                     churn_rate=0.3))
            for ex in ("host", "fleet", "sharded")}
    host = runs["host"]
    for ex in ("fleet", "sharded"):
        assert host.ledger.as_dict() == runs[ex].ledger.as_dict()
        for a, b in zip(jax.tree.leaves(host.final_params),
                        jax.tree.leaves(runs[ex].final_params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-4, rtol=5e-3, err_msg=ex)


def test_rejects_unknown_executor_name():
    spec = _spec("fedavg", "warp", clients=4, rounds=1)
    with pytest.raises(ValueError, match="unknown engine preset 'warp'"):
        run_experiment(spec)
