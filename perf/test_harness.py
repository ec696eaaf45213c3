"""The harness finds cells, configurations, traffic mixes, limits,
per-layer metrics and the schedule references of strategies and scenarios
by file name alone, and refuses to run off a TPU."""
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from perf import run as RUN

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH = RUN.manifest()
CHECKED = {"loss_gap", "grad_gap", "grad_diff", "change_gap",
           "ledger_gap", "schedule_faults"}


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    from perf import harness as H
    from perf.traffic.generate import load_mix
    c = RUN.find_cell(BENCH, cell)
    conf, ref, glue = H.load_config(c["config"])
    assert conf["name"] == c["config"]
    for fn in ("init", "loss", "train_flops_per_row"):
        assert callable(getattr(ref, fn))
    assert callable(glue.program)
    mix = load_mix(c["traffic"])
    from perf import reference as R
    assert callable(R.strategy_ref(mix["strategy"]).plan)
    assert callable(R.world_ref(mix["scenario"]).round_channels)
    limits = set(RUN.limits_of(cell))
    assert {"ledger_gap", "schedule_faults"} <= limits <= CHECKED
    for m in RUN.cell_metrics(BENCH, cell, "per_layer"):
        assert callable(importlib.import_module(f"perf.metrics.{m['name']}")
                        .read)


def _env(extra_path=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if extra_path:
        env["PYTHONPATH"] = extra_path
    return env


def test_new_files_are_found_without_editing(tmp_path):
    tree = tmp_path / "perf"
    shutil.copytree(HERE, tree, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata", "test_*.py"))
    cfg = tree / "configs"
    for ext in (".json", "_ref.py", ".py"):
        shutil.copy(cfg / f"cnn_fmnist{ext}", cfg / f"extra_conf{ext}")
    shutil.copy(tree / "traffic" / "feddif_n256.json",
                tree / "traffic" / "extra_mix.json")
    (tree / "metrics" / "extra_metric.py").write_text(
        "def read(ctx):\n    return ctx['rounds']\n")
    shutil.copy(tree / "strategies" / "feddif.py",
                tree / "strategies" / "extra_strategy.py")
    shutil.copy(tree / "worlds" / "static.py",
                tree / "worlds" / "extra_world.py")
    code = ("from perf import harness as H\n"
            "from perf.traffic.generate import load_mix\n"
            "import importlib\n"
            "conf, ref, glue = H.load_config('extra_conf')\n"
            "assert conf['params'] == 206874 and callable(glue.program)\n"
            "assert load_mix('extra_mix')['clients'] == 256\n"
            "m = importlib.import_module('perf.metrics.extra_metric')\n"
            "assert m.read({'rounds': 7}) == 7\n"
            "from perf import reference as R\n"
            "assert R.strategy_ref('extra_strategy').d2d_rounds("
            "{'max_diffusion_rounds': 3}) == 3\n"
            "up, d2d = R.world_ref('extra_world').round_channels(1, 0, 5, 2)\n"
            "assert up.shape == (5,) and len(d2d) == 2\n"
            "print('found')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=_env(str(tmp_path)), capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "found"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_off_tpu_exits_nonzero_and_prints_no_metrics(trace):
    cell = BENCH["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 17), "--seconds", "1", "--trace", trace],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "{" not in out.stdout and "metrics" not in out.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = BENCH["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_manifest_names_only_files_under_paths():
    paths = BENCH["paths"]
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] == \
            c["name"]
    assert BENCH["command"][1].split("/")[0] in paths
