"""2-D ``("clients", "model")`` mesh executor: factorization, non-divisible
padding, and N=256 parity on a forced 8-device CPU mesh.

Tier-1 runs on one CPU device where ``make_fl_mesh`` degenerates to a
``(1, 1)`` mesh; the multi-device behaviour (model-axis ring shifts, padded
client shards, all_to_all reshards) is exercised in subprocesses that force
``XLA_FLAGS=--xla_force_host_platform_device_count=K`` before the first jax
import — the same topology CI's mesh2d job drives.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
from repro.fl.executors import _chunked_permutation_tables
from repro.launch.mesh import make_fl_mesh


def _run_forced(code: str, devices: int, timeout: int = 600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# ------------------------------------------------------------ mesh factory

def test_make_fl_mesh_degenerates_on_one_device():
    mesh = make_fl_mesh(64, model=4, max_devices=1)
    assert dict(mesh.shape) == {"clients": 1, "model": 1}
    assert tuple(mesh.axis_names) == ("clients", "model")


def test_make_fl_mesh_factorizes_forced_devices():
    """On an 8-device mesh the model axis takes the largest divisor ≤ the
    request and the client axis the rest, clamped to N."""
    code = """
from repro.launch.mesh import make_fl_mesh
shapes = {
    "m1": dict(make_fl_mesh(64).shape),
    "m2": dict(make_fl_mesh(64, model=2).shape),
    "m3": dict(make_fl_mesh(64, model=3).shape),   # 3 ∤ 8 -> falls back to 2
    "m8": dict(make_fl_mesh(64, model=8).shape),
    "small_n": dict(make_fl_mesh(3).shape),        # never > N client shards
}
assert shapes["m1"] == {"clients": 8, "model": 1}, shapes
assert shapes["m2"] == {"clients": 4, "model": 2}, shapes
assert shapes["m3"] == {"clients": 4, "model": 2}, shapes
assert shapes["m8"] == {"clients": 1, "model": 8}, shapes
assert shapes["small_n"] == {"clients": 3, "model": 1}, shapes
print("MESH_FACTORIZATION_OK")
"""
    assert "MESH_FACTORIZATION_OK" in _run_forced(code, 8, timeout=120)


# ------------------------------------------------- chunked hop routing table

@pytest.mark.parametrize("c,k,chunks", [(8, 2, 2), (16, 4, 2), (12, 2, 3)])
def test_chunked_permutation_tables_route_every_row(c, k, chunks):
    """Replaying the per-chunk send/recv tables in numpy reproduces
    take(x, perm) chunk by chunk — the double-buffered hop's invariant that
    chunk j+1's sends never read rows chunk j already overwrote."""
    rng = np.random.default_rng(c + k + chunks)
    nl, mb = c // k, c // k // chunks
    for _ in range(5):
        perm = rng.permutation(c)
        send, recv = _chunked_permutation_tables(perm, k, chunks)
        x = np.arange(c)
        out = np.full((k, nl), -1)
        for j in range(chunks):
            buf_out = np.full((k, mb + 1), -1)     # chunk block + trash row
            for shift in range(k):
                for s in range(k):
                    d = (s + shift) % k
                    buf = x[s * nl:(s + 1) * nl][send[s, j, shift]]
                    buf_out[d][recv[d, j, shift]] = buf
            out[:, j * mb:(j + 1) * mb] = buf_out[:, :mb]
        np.testing.assert_array_equal(out.ravel(), x[perm])


# --------------------------------------- non-divisible shapes (padded shards)

def test_nondivisible_clients_and_model_axis_keep_parity():
    """N=10 on a 4-device mesh (client axis pads 10→12 slots) and the same
    N with a 2-way model axis (flattened feature count padded to an even
    split) must both reproduce the host plane: identical ledgers, matching
    params — padding slots carry zero aggregation weight and never leak."""
    code = """
import numpy as np, jax
assert len(jax.devices()) == 4, jax.devices()
from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
def spec(executor, **kw):
    return ExperimentSpec(task="fcn", alpha=0.5, num_samples=1000,
        fl=FLConfig(strategy="feddif", rounds=2, num_clients=10,
                    num_models=10, seed=0, topology_seed=1,
                    max_diffusion_rounds=3,
                    engine=EngineSpec(mode=executor, **kw)))
host = run_experiment(spec("host"))
for label, kw in (("pad_clients", {"shard_overlap": "on"}),
                  ("pad_model", {"shard_overlap": "on",
                                 "mesh_model_axis": 2})):
    r = run_experiment(spec("sharded", **kw))
    assert host.ledger.as_dict() == r.ledger.as_dict(), label
    assert host.diffusion_rounds == r.diffusion_rounds, label
    for a, b in zip(jax.tree.leaves(host.final_params),
                    jax.tree.leaves(r.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3, err_msg=label)
print("NONDIVISIBLE_PARITY_OK")
"""
    assert "NONDIVISIBLE_PARITY_OK" in _run_forced(code, 4)


# ------------------------------------------------ N=256 parity on a 2-D mesh

def test_n256_parity_on_2d_mesh_8_devices():
    """The acceptance topology: N=256 on a forced 8-device (4×2) mesh with a
    2-way model axis, overlapped (fused) and op-by-op planes both matching
    the single-device fleet reference bit-for-bit on the ledger."""
    code = """
import numpy as np, jax
assert len(jax.devices()) == 8, jax.devices()
from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
def spec(executor, **kw):
    return ExperimentSpec(task="fcn", alpha=0.5, num_samples=25600,
        fl=FLConfig(strategy="feddif", rounds=1, num_clients=256,
                    num_models=256, seed=0, topology_seed=1,
                    max_diffusion_rounds=2,
                    engine=EngineSpec(mode=executor, **kw)))
fleet = run_experiment(spec("fleet"))
for label, kw in (("fused", {"shard_overlap": "on", "mesh_model_axis": 2}),
                  ("op_by_op", {"mesh_model_axis": 2, "shard_overlap": "off"})):
    r = run_experiment(spec("sharded", **kw))
    assert fleet.ledger.as_dict() == r.ledger.as_dict(), label
    assert fleet.diffusion_rounds == r.diffusion_rounds, label
    for a, b in zip(jax.tree.leaves(fleet.final_params),
                    jax.tree.leaves(r.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3, err_msg=label)
print("N256_MESH2D_PARITY_OK")
"""
    assert "N256_MESH2D_PARITY_OK" in _run_forced(code, 8)


# ------------------------------------------------- single-device 2-D configs

def test_mesh_model_axis_degenerates_cleanly_on_one_device():
    """mesh_model_axis > 1 on a single-device host must be a no-op (the mesh
    clamps to (1, 1)) — same ledger and params as the plain sharded run."""
    def spec(**kw):
        return ExperimentSpec(
            task="fcn", alpha=0.5, num_samples=800,
            fl=FLConfig(strategy="feddif", rounds=1, num_clients=8,
                        num_models=8, seed=0, topology_seed=1,
                        max_diffusion_rounds=2,
                        engine=EngineSpec(mode="sharded", **kw)))
    base = run_experiment(spec())
    m2 = run_experiment(spec(mesh_model_axis=4))
    assert base.ledger.as_dict() == m2.ledger.as_dict()
    for a, b in zip(jax.tree.leaves(base.final_params),
                    jax.tree.leaves(m2.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3)
