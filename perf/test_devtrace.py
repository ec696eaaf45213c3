"""The reduction from trace to metrics, on events built by hand and on a
small trace recorded on one TPU v5e (``testdata/small_trace.xplane.pb``:
three rounds of a jitted ``tanh(a) @ a.T``, the Pallas ``mix_aggregate``
kernel, and ``exec``/``plan`` spans)."""
import os

import numpy as np
import pytest

from perf import devtrace as T

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "testdata", "small_trace.xplane.pb")


@pytest.mark.parametrize("iv,want", [
    ([], 0),
    ([(0, 10)], 10),
    ([(0, 10), (5, 15)], 15),              # overlap counted once
    ([(0, 10), (10, 20)], 20),             # touching
    ([(20, 30), (0, 10), (2, 3)], 20),     # unsorted, nested
])
def test_busy_union(iv, want):
    assert T.union_ns(iv) == want


def test_gaps_between_busy_intervals():
    s, e = np.array([30, 10, 12]), np.array([40, 20, 15])
    g0, g1 = T.gaps_ns(s, e, 0, 50)
    assert list(zip(g0.tolist(), g1.tolist())) == [(0, 10), (20, 30),
                                                   (40, 50)]
    g0, g1 = T.gaps_ns(np.array([0]), np.array([50]), 0, 50)
    assert len(g0) == 0


def _ev(*triples):
    return T.Events.of((n, s, e - s) for n, s, e in triples)


def test_gap_labelled_by_innermost_open_span():
    spans = {"exec": _ev(("exec", 0, 100)), "batch": _ev(("batch", 10, 30)),
             "plan": _ev(("plan", 200, 300))}
    mids = np.array([20, 50, 215, 130])
    assert T.label_gaps(mids, spans) == ["batch", "exec", "plan",
                                         "outside spans"]


def _synthetic():
    ops = {0: _ev(("fusion.1", 0, 40), ("_mix_kernel", 60, 80)),
           1: _ev(("fusion.1", 10, 50), ("_mix_kernel", 60, 70))}
    mods = {0: _ev(("jit_one", 0, 40), ("jit_mix", 60, 80)),
            1: _ev(("jit_one", 10, 50), ("jit_mix", 60, 70))}
    spans = {"exec": _ev(("exec", 0, 100))}
    return T.reduce_events(ops, mods, spans, devices=2)


def test_idle_share_and_program_time():
    red = _synthetic()
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx((60 + 50) / 2 * 1e-9)
    assert red.module_time(r"^jit_one$") == pytest.approx(40e-9)
    assert red.op_time(r"_mix_kernel") == pytest.approx(15e-9)
    assert red.module_time(r"^absent$") is None
    b = red.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0][0] == "exec"


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        T.reduce_events({0: _ev()}, {}, {}, devices=1)


def test_recorded_trace():
    red = T.reduce_file(SMALL, devices=1)
    assert 0 < red.busy_s < red.window_s
    assert red.op_time(r"_mix_kernel|mix_aggregate") > 0
    assert red.module_time(r"mix_aggregate") > 0
    assert red.span_s["exec"] > 0 and red.span_s["plan"] > 0
    assert red.gap_s["plan"] > 0
    assert len(red.breakdown()["device_ops"]) <= 10
