"""The check that decides ``correct``, driven end to end on the CPU at a
small size: everything a run does after its look for a chip, with the
timed path sound, broken underneath in each way the cell can break, and
replaced by the control; and the control's first gradient at the cells'
own fleet, rows and batch.  Every configuration runs at its CPU sizes
(``harness.cpu_sizes``: its ``"cpu"`` object, where it has one), which a
test here holds to a bound.  The limits are the cells' own."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import harness as H
from perf import reference as R
from perf import run as RUN
from perf.metrics.mix_aggregate_roofline import mixed_params
from perf.traffic.generate import (load_mix, make_traffic, row_width,
                                   sub_seeds)

BENCH = RUN.manifest()
SEED = 2 ** 31 + 4321          # more than 32 signed bits hold
# What one configuration may ask of a CPU test: trained and frozen
# parameters together, and the features or token ids of a row.
CPU_PARAMS, CPU_ROW = 2_000_000, 1024


def _small(cell_name):
    cell = RUN.find_cell(BENCH, cell_name)
    mix = load_mix(cell["traffic"])
    conf, _, _ = H.load_config(cell["config"])
    mix.update(clients=6, models=6, rows_per_client=40, test_rows=100)
    return cell, mix, H.cpu_sizes(conf)


def _run(cell_name, fault=None):
    cell, mix, conf = _small(cell_name)
    args = argparse.Namespace(seed=SEED, seconds=1.0, trace=0)
    return RUN.run(args, BENCH, cell, jax.devices()[:1], require_tpu=False,
                   fault=fault, mix=mix, conf=conf)


def _cases():
    """Per cell: the sound run, then each fault it can have."""
    for c in BENCH["workloads"]:
        faults = ["unchanged", "half_batch"]
        if load_mix(c["traffic"])["strategy"] == "feddif":
            faults.append("no_hop")
        yield c["name"], None, True
        for fault in faults:
            yield c["name"], fault, False


CASES = list(_cases())


@pytest.mark.parametrize("cell,fault,correct", CASES)
def test_check_decides(cell, fault, correct):
    out = _run(cell, fault)
    assert out["correct"] is correct, out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) >= {"setup_s", "round_s"}
    assert out["attempted"] >= 1


CELLS = [c["name"] for c in BENCH["workloads"]]


def _control_numbers(cell_d, mix, conf, seed, schedules=None, rec=None):
    """The control's numbers against the reference: the reference in the
    next precision below the configuration's, put in the program's place.
    Without ``schedules`` only the first gradients are compared."""
    _, ref, glue = H.load_config(cell_d["config"])
    ctl = glue.CONTROL
    traffic = make_traffic(mix, conf["data"], seed)
    seeds = sub_seeds(seed)
    frozen = H.frozen_tree(conf, ref, seeds)
    if schedules is None:
        params0 = ref.init(conf, jax.random.PRNGKey(seeds["init"]))
        grads = [R.first_gradient(mix, traffic, seeds["loader"], params0,
                                  R.Trainer(ref, conf, mix, jnp.dtype(dt),
                                            jnp.float32, glue.SLOT_BLOCK,
                                            frozen))
                 for dt in ("float32", ctl["compute_dtype"])]
        n_ctl, n_ref, diff = R.grad_norms(*grads)
        keep = n_ref >= 1e-3 * np.median(n_ref)
        med = float(np.median(n_ref[keep]))
        return {"grad_gap": R.norm_gap(n_ctl, n_ref, keep),
                "grad_diff": float(np.max((diff / np.maximum(n_ref, med))
                                          [keep]))}
    params0, ref_g, grad1, found, _ = H.reference_globals(
        conf, ref, glue, mix, traffic, seeds, schedules, frozen=frozen)
    assert not found
    _, ctl_g, ctl_grad1, _, _ = H.reference_globals(
        conf, ref, glue, mix, traffic, seeds, schedules,
        compute_dtype=ctl["compute_dtype"], param_dtype=ctl["param_dtype"],
        frozen=frozen)
    return R.compare(
        R.reading_of(ref, conf, traffic, params0, ctl_g, ctl_grad1,
                     glue.EVAL_BLOCK, jnp.dtype(ctl["compute_dtype"]),
                     frozen),
        R.reading_of(ref, conf, traffic, params0, ref_g, grad1,
                     glue.EVAL_BLOCK, frozen=frozen))


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """At a small size the control fails at least one compared number."""
    cell_d, mix, conf = _small(cell)
    _, ref, glue = H.load_config(cell_d["config"])
    rec, _, _, _ = H.drive(conf, ref, glue, mix, SEED, 0.0, None,
                           stop_after=H.WARMUP_ROUNDS)
    numbers = _control_numbers(cell_d, mix, conf, SEED, rec.schedules)
    limits = RUN.limits_of(cell)
    assert any(numbers[k] > v for k, v in limits.items() if k in numbers), \
        numbers


@pytest.mark.parametrize("cell", CELLS)
def test_control_first_gradient_fails_at_the_cells_size(cell):
    """At the cell's own fleet, rows and batch, and the configuration's
    CPU sizes, the control's first gradient is over the cell's limit."""
    cell_d = RUN.find_cell(BENCH, cell)
    conf = H.cpu_sizes(H.load_config(cell_d["config"])[0])
    mix = load_mix(cell_d["traffic"])
    numbers = _control_numbers(cell_d, mix, conf, SEED + 1)
    assert numbers["grad_diff"] > RUN.limits_of(cell)["grad_diff"], numbers


def test_schedule_check_catches_a_wrong_weight():
    cell, mix, conf = _small("cnn_feddif_n256")
    _, ref, glue = H.load_config(cell["config"])
    rec, traffic, _, seeds = H.drive(conf, ref, glue, mix,
                                     SEED, 0.0, None, stop_after=1)
    sched = rec.schedules[0]
    sched.agg[0] = (sched.agg[0][0], sched.agg[0][1] + 1.0)
    up, d2d = R.world_ref(mix["scenario"]).round_channels(
        seeds["topology"], 0, mix["clients"], mix["max_diffusion_rounds"])
    plan = R.check_schedule(sched, mix, traffic.part.data_sizes, up, d2d,
                            1.0, R.Ledger())
    assert plan.faults


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_fits_the_cpu_at_its_cpu_sizes(name):
    """The per-cell tests run every cell at its configuration's CPU sizes:
    trained and frozen trees of at most ``CPU_PARAMS`` parameters between
    them, rows of at most ``CPU_ROW`` features or token ids."""
    conf, ref, _ = H.load_config(name)
    conf = H.cpu_sizes(conf)
    trees = [jax.eval_shape(lambda: ref.init(conf, jax.random.PRNGKey(0)))]
    if hasattr(ref, "frozen"):
        trees.append(jax.eval_shape(
            lambda: ref.frozen(conf, jax.random.PRNGKey(0))))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(trees))
    assert count <= CPU_PARAMS, count
    assert row_width(conf["data"]) <= CPU_ROW


def test_cpu_sizes_merge_deeply():
    conf = {"layers": 5, "width": 2048,
            "data": {"kind": "tokens", "seq": 1024, "vocab": 20480},
            "cpu": {"layers": 2, "data": {"seq": 32}}}
    small = H.cpu_sizes(conf)
    assert small["layers"] == 2 and small["width"] == 2048
    assert small["data"] == {"kind": "tokens", "seq": 32, "vocab": 20480}
    assert conf["data"]["seq"] == 1024
    plain = {"layers": 5, "data": {"kind": "image", "dim": 784}}
    assert H.cpu_sizes(plain) == plain


def test_mixed_tree_size_is_the_trained_trees():
    """``mix_aggregate_roofline``'s F for ``cnn_fmnist`` is its whole
    model: nothing is frozen."""
    conf, ref, _ = H.load_config("cnn_fmnist")
    assert mixed_params(conf, ref) == conf["params"] == 206874
