"""A configuration with a frozen base tree and token rows runs from new
files alone.  The test-only ``lm_lora`` (``perf/testdata``: the program's
``lm`` task trained as LoRA adapters over a frozen base) is copied with a
token traffic mix and a cell into a tree of the benchmark's own files, as
a later PR would add them, and driven there on the CPU: the sound run is
``correct``, each fault of the timed path is caught, the control fails a
limit, the mixed tree is the adapters, and the frozen tree reaches the
reference's step as an argument, not as a constant."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA = os.path.join(HERE, "testdata")
SEED = 2 ** 31 + 4321          # more than 32 signed bits hold
CELL = {"name": "lm_lora_feddif_c6", "config": "lm_lora",
        "traffic": "tokens_feddif_c6", "chips": 1,
        "why": "FedDif over 6 clients, 24 token rows each, batch 8, seq 16: "
               "LoRA adapters hop and mix over a frozen base"}
PLACES = {"lm_lora.json": "configs", "lm_lora.py": "configs",
          "lm_lora_ref.py": "configs", "tokens_feddif_c6.json": "traffic",
          "lm_lora_feddif_c6.json": "cells"}

DRIVE = f"""
import argparse, json, re
import jax, jax.numpy as jnp
from perf import harness as H, readings, reference as R, run as RUN
from perf.metrics.mix_aggregate_roofline import mixed_params
from perf.traffic.generate import load_mix

bench = RUN.manifest()
cell = RUN.find_cell(bench, {CELL["name"]!r})
limits = RUN.limits_of(cell["name"])
args = argparse.Namespace(seed={SEED}, seconds=1.0, trace=0)
out = {{"correct": {{}}}}
for fault in (None, "unchanged", "half_batch", "no_hop"):
    res = RUN.run(args, bench, cell, jax.devices()[:1], require_tpu=False,
                  fault=fault)
    out["correct"][str(fault)] = res["correct"]
    out.setdefault("checks", {{}})[str(fault)] = res["check"]
ctl = readings.read_seed(cell, {SEED}, control=True, faults=False)["control"]
out["control"] = ctl
out["control_fails"] = sorted(k for k, v in limits.items()
                              if k in ctl and ctl[k] > v)

conf, ref, glue = H.load_config(cell["config"])
key = jax.random.PRNGKey(0)
size = lambda t: sum(a.size for a in jax.tree.leaves(t))
out["mixed"] = mixed_params(conf, ref)
out["adapters"] = size(ref.init(conf, key))
out["frozen"] = size(ref.frozen(conf, key))

def lowered(vocab):
    c = dict(conf, vocab=vocab)
    mix = load_mix(cell["traffic"])
    n, b, s = glue.SLOT_BLOCK, mix["batch_size"], c["data"]["seq"] + 1
    fz = ref.frozen(c, key)
    p = jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                     ref.init(c, key))
    mu = jax.tree.map(jnp.zeros_like, p)
    step = R.Trainer(ref, c, mix, jnp.float32, jnp.float32, n, fz).step
    text = step.lower(
        p, mu, jnp.zeros((n, b, s), jnp.int32), jnp.zeros((n, b), jnp.int32),
        jnp.ones(n, bool), fz).as_text()
    return [size(fz), len(re.sub(r"[0-9]+", "0", text))]

out["lowered"] = [lowered(128), lowered(512)]
print(json.dumps(out))
"""


def _tree(tmp_path):
    """The benchmark's files, with the test-only configuration, its
    traffic and its cell added under their own names, and the cell in
    ``BENCHMARK.json``."""
    shutil.copytree(HERE, tmp_path / "perf", ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", "test_*.py"))
    for name, place in PLACES.items():
        shutil.copy(os.path.join(DATA, name), tmp_path / "perf" / place)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "lm_lora", "source": "perf/testdata",
                             "file": "perf/configs/lm_lora.json",
                             "reduced": [], "why": "test only"})
    bench["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def test_frozen_base_token_config_runs_from_new_files(tmp_path):
    _tree(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] == {"None": True, "unchanged": False,
                              "half_batch": False, "no_hop": False}, \
        out["checks"]
    assert out["control_fails"], out["control"]
    # The fleet mixes the adapters alone: F is theirs, not the model's.
    assert out["mixed"] == out["adapters"] == 2 * (
        4 * 2 * (64 + 64) + 2 * 2 * (64 + 128))
    assert out["frozen"] > 10 * out["adapters"]
    # A frozen tree four times the size leaves the reference's step as
    # long, with every number in its text written as one digit (shapes
    # name the vocabulary): the tree is an argument, never a constant.
    (small, small_text), (big, big_text) = out["lowered"]
    assert big == small + (512 - 128) * 64
    assert big_text <= small_text, out["lowered"]
