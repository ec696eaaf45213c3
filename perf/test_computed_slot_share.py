"""``computed_slot_share``: the training slot-steps over those the step
computed, from the program's counters, falling back to every slot of the
fleet where the program counts no computed slots."""
import sys

import pytest

from perf.metrics import computed_slot_share as M
from perf.metrics import slot_share
from repro.obs import Record


def _ctx(**counters):
    return {"rounds": 1, "program": Record(
        spans=[], counters={f"fl.exec.{k}": v for k, v in counters.items()},
        compiles={})}


def test_it_reads_the_computed_slots():
    ctx = _ctx(slot_steps=288, computed_slot_steps=240,
               active_slot_steps=213)
    assert M.read(ctx) == pytest.approx(100 * 213 / 240)
    assert slot_share.read(ctx) == pytest.approx(100 * 213 / 288)


def test_without_the_counter_it_reads_the_slot_share():
    ctx = _ctx(slot_steps=288, active_slot_steps=213)
    assert M.read(ctx) == pytest.approx(slot_share.read(ctx))
    assert M.read(ctx) == pytest.approx(100 * 213 / 288)


def test_a_fleet_that_computes_every_slot_reads_100():
    assert M.read(_ctx(slot_steps=3584, computed_slot_steps=3584,
                       active_slot_steps=3584)) == 100.0


@pytest.mark.parametrize("counters", [{}, {"active_slot_steps": 5}])
def test_it_reports_nothing_without_slots(counters):
    assert M.read(_ctx(**counters)) is None


def test_it_reports_nothing_without_the_programs_record(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert M.read({"rounds": 3}) is None
