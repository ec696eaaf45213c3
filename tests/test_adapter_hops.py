"""The adapter hop plane end to end: frozen-base/LoRA views on the "lm"
task, int8-packed PermuteOp wire, cross-executor parity (host / fleet /
sharded, ring and gather transports, fused round plane), the Eq.-15 ledger
decomposition with ``spec_adapter_bits``, and the full-params degenerate
path staying bit-identical for the CNN sweeps."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
from repro.fl.adapters import make_adapter_view, packed_bits
from repro.fl.experiment import spec_adapter_bits, spec_model_bits
from repro.fl.models import build_task_model


def _spec(engine="host", task="lm", hop_quant="int8", adapter_hops=True,
          clients=4, rounds=2, **fl_kw):
    return ExperimentSpec(
        task=task, alpha=0.5, dim=16 if task == "lm" else 64,
        num_samples=640, adapter_hops=adapter_hops,
        fl=FLConfig(strategy="feddif", rounds=rounds, num_clients=clients,
                    num_models=clients, seed=0, topology_seed=1,
                    max_diffusion_rounds=3, engine=engine,
                    hop_quant=hop_quant, **fl_kw))


def _run_forced(code: str, devices: int, timeout: int = 600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# -------------------------------------------- cross-executor quant parity

def test_host_fleet_sharded_parity_int8_lm():
    """One pack→unpack roundtrip per hop per slot on every plane: ledgers
    bit-identical, adapters within the executor-parity tolerance."""
    host = run_experiment(_spec("host"))
    fleet = run_experiment(_spec("fleet"))
    sharded = run_experiment(_spec(EngineSpec(mode="sharded",
                                              shard_overlap="on")))
    assert (host.ledger.as_dict() == fleet.ledger.as_dict()
            == sharded.ledger.as_dict())
    assert host.diffusion_rounds == fleet.diffusion_rounds
    for r in (fleet, sharded):
        for a, b in zip(jax.tree.leaves(host.final_params),
                        jax.tree.leaves(r.final_params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-4, rtol=5e-3)


def test_sharded_transports_and_planes_parity_2_devices():
    """On a real 2-device client mesh the packed wire rides the ring
    ppermute, the gather all_gather and the fused (overlapped) round plane
    — all three must reproduce the fleet reference."""
    code = """
import numpy as np, jax
assert len(jax.devices()) == 2, jax.devices()
from repro.fl import EngineSpec, ExperimentSpec, FLConfig, run_experiment
def spec(executor, **kw):
    return ExperimentSpec(task="lm", alpha=0.5, dim=16, num_samples=640,
        fl=FLConfig(strategy="feddif", rounds=2, num_clients=4,
                    num_models=4, seed=0, topology_seed=1,
                    max_diffusion_rounds=3,
                    engine=EngineSpec(mode=executor, **kw),
                    hop_quant="int8"))
fleet = run_experiment(spec("fleet"))
for label, kw in (("ring_fused", {"shard_overlap": "on",
                                  "shard_hop_transport": "ring"}),
                  ("gather_fused", {"shard_overlap": "on",
                                    "shard_hop_transport": "gather"}),
                  ("op_by_op", {"shard_overlap": "off"})):
    r = run_experiment(spec("sharded", **kw))
    assert fleet.ledger.as_dict() == r.ledger.as_dict(), label
    assert fleet.diffusion_rounds == r.diffusion_rounds, label
    for a, b in zip(jax.tree.leaves(fleet.final_params),
                    jax.tree.leaves(r.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-3, err_msg=label)
print("ADAPTER_INT8_TRANSPORT_PARITY_OK")
"""
    assert "ADAPTER_INT8_TRANSPORT_PARITY_OK" in _run_forced(code, 2)


# --------------------------------------------------- frozen-base property

def test_frozen_base_bit_identical_through_diffusion():
    """Diffusion rounds move only the adapter: the merged full model's base
    leaves are bitwise the round-0 broadcast, while the LoRA leaves moved
    (b is zero-init, so any training shows up there)."""
    spec = _spec("host")
    r = run_experiment(spec)
    model = build_task_model(spec.task, spec.dim, spec.num_classes)
    view = make_adapter_view(model, spec.fl)
    base0, adapter0 = model.split(
        model.init(jax.random.PRNGKey(spec.fl.seed)))
    for a, b in zip(jax.tree.leaves(view.base), jax.tree.leaves(base0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full = view.merge_fn(r.final_params)
    base_f, adapter_f = model.split(full)
    for a, b in zip(jax.tree.leaves(base_f), jax.tree.leaves(base0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(adapter_f),
                        jax.tree.leaves(adapter0)))
    assert moved, "training/diffusion must move the adapter"


def test_full_params_tasks_unaffected_by_adapter_flag():
    """No-split tasks get the identity view: adapter_hops on/off is the
    same program — bit-identical ledger AND params (the CNN-sweep
    bit-compat guarantee)."""
    on = run_experiment(_spec("host", task="fcn", hop_quant="none",
                              adapter_hops=True))
    off = run_experiment(_spec("host", task="fcn", hop_quant="none",
                               adapter_hops=False))
    assert on.ledger.as_dict() == off.ledger.as_dict()
    for a, b in zip(jax.tree.leaves(on.final_params),
                    jax.tree.leaves(off.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- ledger accounting

def test_ledger_charges_packed_adapter_bits():
    """transmitted_bits decomposes exactly into uplinks·(adapter fp32) +
    D2D hops·(int8-packed adapter); the round-0 base broadcast adds one
    downlink_models count (not bits-charged as a hop)."""
    spec = _spec("host")
    r = run_experiment(spec)
    led = r.ledger.as_dict()
    hop_bits = spec_adapter_bits(spec)
    view_f32 = spec_adapter_bits(dataclasses.replace(
        spec, fl=dataclasses.replace(spec.fl, hop_quant="none")))
    d2d = led["transmitted_models"] - led["uplink_models"]
    assert d2d > 0, "feddif must schedule D2D hops in this cell"
    expected = led["uplink_models"] * view_f32 + d2d * hop_bits
    np.testing.assert_allclose(led["transmitted_bits"], expected, rtol=1e-9)
    # one extra downlink: the round-0 frozen-base broadcast
    assert led["downlink_models"] == spec.fl.rounds + 1
    full = run_experiment(_spec("host", task="fcn", hop_quant="none",
                                adapter_hops=False))
    assert full.ledger.as_dict()["downlink_models"] == spec.fl.rounds


def test_spec_adapter_bits_relations():
    lm = _spec("host")
    lm_f32 = dataclasses.replace(
        lm, fl=dataclasses.replace(lm.fl, hop_quant="none"))
    full = dataclasses.replace(lm_f32, adapter_hops=False)
    b_int8 = spec_adapter_bits(lm)
    b_f32 = spec_adapter_bits(lm_f32)
    b_full = spec_adapter_bits(full)
    assert b_int8 < b_f32 < b_full
    assert b_full == spec_model_bits(lm)
    assert b_full / b_int8 >= 50.0           # the headline payload claim
    model = build_task_model("lm", lm.dim, lm.num_classes)
    _, adapter = model.split(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert b_int8 == packed_bits(adapter)
    # no-split task: spec_adapter_bits degenerates to spec_model_bits
    fcn = _spec("host", task="fcn", hop_quant="none")
    assert spec_adapter_bits(fcn) == spec_model_bits(fcn)


# ---------------------------------------------------------- spec validation

def test_experiment_spec_validates_at_construction():
    with pytest.raises(ValueError, match="unknown task"):
        ExperimentSpec(task="transformer")
    with pytest.raises(ValueError, match="square"):
        ExperimentSpec(task="cnn", dim=60)
    with pytest.raises(ValueError, match="divisible by 8"):
        ExperimentSpec(task="lstm", dim=30)
    with pytest.raises(AssertionError):
        run_experiment(ExperimentSpec(
            task="fcn", num_samples=200,
            fl=FLConfig(rounds=1, num_clients=2, num_models=2,
                        hop_quant="int4")))
