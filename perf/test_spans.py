"""The program's spans in the benchmark: the trace reduction that labels
device idle by the innermost program span, on events built by hand; and
the per-layer metrics that read ``repro.obs``'s record, on a small cell
driven on the CPU under the profiler, and with no record to read."""
import importlib
import sys

import jax
import pytest

from perf import devtrace as T
from perf import harness as H
from perf import run as RUN
from perf import spans as S
from perf.traffic.generate import load_mix

BENCH = RUN.manifest()
SEED = 2 ** 31 + 77
READERS = ("plan_host_ms", "draw_ms", "slot_share")


def _ev(*triples):
    return T.Events.of((n, s, e - s) for n, s, e in triples)


def _program_spans():
    return {"fl.round": _ev(("fl.round", 0, 1000)),
            "fl.plan": _ev(("fl.plan", 0, 400)),
            "fl.plan.auction": _ev(("fl.plan.auction", 100, 300)),
            "fl.exec": _ev(("fl.exec", 400, 900)),
            "fl.exec.draw": _ev(("fl.exec.draw", 400, 500),
                                ("fl.exec.draw", 600, 650))}


def test_self_time_is_less_the_direct_children():
    own = S.self_ns(_program_spans())
    assert own == {"fl.round": 100, "fl.plan": 200, "fl.plan.auction": 200,
                   "fl.exec": 350, "fl.exec.draw": 150}


def test_idle_is_labelled_by_the_innermost_program_span():
    # The device runs the auction (100-300), a transfer and the steps; it
    # idles in the plan's host stretches (0-100, 300-350), in both draws
    # (400-500, 600-650), in the executor itself (680-700) and in the rest
    # of the round (900-1000).  Each gap goes to the innermost span open at
    # its midpoint.
    ops = {0: _ev(("while", 100, 300), ("h2d", 350, 400),
                  ("step", 500, 600), ("step", 650, 680),
                  ("step", 700, 900))}
    table = S.reduce_events(ops, _program_spans(), 1)
    idle = {k: v["idle_s"] * 1e9 for k, v in table.items()}
    assert idle == pytest.approx({
        "fl.round": 100, "fl.plan": 150, "fl.plan.auction": 0,
        "fl.exec": 20, "fl.exec.draw": 150, "outside spans": 0})
    assert table["fl.exec.draw"]["calls"] == 2
    assert table["fl.exec.draw"]["host_s"] * 1e9 == pytest.approx(150)
    assert table["fl.exec"]["self_s"] * 1e9 == pytest.approx(350)


def test_readers_report_nothing_without_the_programs_record(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)       # as the parent
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in READERS:
        mod = importlib.import_module(f"perf.metrics.{name}")
        assert mod.read({"rounds": 3}) is None


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_readers_read_a_small_cell_driven_under_the_profiler(cell, tmp_path,
                                                             capsys):
    from repro import obs
    obs.snapshot()
    c = RUN.find_cell(BENCH, cell)
    conf, ref, glue = H.load_config(c["config"])
    conf = H.cpu_sizes(conf)
    mix = load_mix(c["traffic"])
    mix.update(clients=6, models=6, rows_per_client=40, test_rows=100)
    with jax.profiler.trace(str(tmp_path)):
        H.drive(conf, ref, glue, mix, SEED, 0.0, None, stop_after=2)
    ctx = {"rounds": 2}
    got = {name: importlib.import_module(f"perf.metrics.{name}").read(ctx)
           for name in READERS}
    assert got["plan_host_ms"] > 0 and got["draw_ms"] > 0
    feddif = mix["strategy"] == "feddif"
    assert (0 < got["slot_share"] < 100) if feddif else \
        got["slot_share"] == 100.0
    rec = ctx["program"]
    assert rec.totals()["fl.round"][0] == 2
    assert ("fl.plan.auction" in rec.totals()) == feddif
    assert "fl.exec.draw" in capsys.readouterr().err
