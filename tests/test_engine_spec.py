"""EngineSpec / RunResult API tests (repro.fl.engine).

The spec is the single engine-selection authority: presets, the one
``FLConfig.engine`` field, auto resolution/downgrade, the executor knobs
it hands over, and the RunResult legacy surface (flat FLResult attributes +
tuple unpacking).
"""
import dataclasses

import pytest

from repro.fl.engine import (ENGINE_PRESETS, AsyncSpec, EngineSpec,
                             SHARDED_CROSSOVER_N, RunHistory, RunResult,
                             engine_fingerprint, resolve_engine)
from repro.fl.server import FLConfig


# ----------------------------------------------------------------- presets

def test_presets_cover_every_mode():
    assert {"host", "fleet", "sharded", "auto", "async",
            "async_barrier"} <= set(ENGINE_PRESETS)
    for name, spec in ENGINE_PRESETS.items():
        spec.validate()


def test_preset_lookup_and_unknown_name():
    assert EngineSpec.preset("fleet").mode == "fleet"
    with pytest.raises(ValueError, match="unknown engine preset"):
        EngineSpec.preset("warp_drive")


def test_async_preset_is_buffered_and_barrier_preset_is_not():
    a = ENGINE_PRESETS["async"].buffered
    b = ENGINE_PRESETS["async_barrier"].buffered
    assert a.buffer_frac is not None and a.staleness_beta > 0
    assert b.buffer_k is None and b.buffer_frac is None
    # same delay model on both arms: the gap isolates buffering
    assert a.delay_scale == b.delay_scale
    assert a.delay_sigma == b.delay_sigma


# ----------------------------------------------------------- resolve order

def test_engine_defaults_to_the_host_preset():
    cfg = FLConfig(strategy="fedavg", num_clients=4, num_models=4)
    assert cfg.engine == "host"
    assert resolve_engine(cfg) == ENGINE_PRESETS["host"] == EngineSpec()
    cfg = dataclasses.replace(cfg, engine=EngineSpec(mode="fleet",
                                                     planner="jax"))
    assert resolve_engine(cfg) == EngineSpec(mode="fleet", planner="jax")


@pytest.mark.parametrize("field,value", [
    ("executor", "fleet"), ("planner", "jax"), ("shard_microbatch", 8),
    ("mesh_model_axis", 2), ("shard_overlap", "on"),
    ("shard_hop_transport", "ring")])
def test_flconfig_rejects_retired_engine_keywords(field, value):
    """The engine is chosen through ``FLConfig.engine`` alone."""
    with pytest.raises(TypeError, match=field):
        FLConfig(strategy="fedavg", num_clients=4, num_models=4,
                 **{field: value})
    assert [f.name for f in dataclasses.fields(FLConfig)
            if f.name in ("engine", field)] == ["engine"]


def test_bad_engine_type_raises():
    cfg = FLConfig(strategy="fedavg", num_clients=4, num_models=4,
                   engine=42)
    with pytest.raises(TypeError, match="EngineSpec or a preset"):
        resolve_engine(cfg)


# ------------------------------------------------------------- auto logic

def test_auto_resolves_by_size_and_never_touches_explicit_modes():
    assert EngineSpec(mode="auto").auto(4).mode == "fleet"
    for mode in ("host", "fleet", "async"):
        assert EngineSpec(mode=mode).auto(4).mode == mode
        assert EngineSpec(mode=mode).auto(10 ** 6).mode == mode


def test_auto_downgrades_sharded_below_crossover():
    small = EngineSpec(mode="sharded").auto(SHARDED_CROSSOVER_N - 1)
    assert small.mode == "fleet"
    # at/above the crossover an explicit sharded request survives
    big = EngineSpec(mode="sharded").auto(SHARDED_CROSSOVER_N)
    assert big.mode == "sharded"


def test_resolve_engine_keeps_explicit_sharded():
    # Benches deliberately run sharded below the crossover for parity
    # checks; only the sweep orchestrator downgrades.
    cfg = FLConfig(strategy="fedavg", num_clients=4, num_models=4,
                   engine=EngineSpec(mode="sharded"))
    assert resolve_engine(cfg).mode == "sharded"


def test_make_executor_takes_the_sharded_knobs_from_the_spec():
    from repro.fl.executors import make_executor
    cfg = FLConfig(strategy="fedavg", num_clients=8, num_models=8)
    spec = EngineSpec(mode="sharded", shard_microbatch=2,
                      shard_overlap="on", shard_hop_transport="ring")
    ex = make_executor("sharded", lambda p, b: 0.0, None, [], cfg, spec)
    assert (ex.mb, ex.overlap, ex._transport_req) == (2, True, "ring")
    ex = make_executor("sharded", lambda p, b: 0.0, None, [], cfg,
                       EngineSpec(mode="sharded"))
    assert (ex.mb, ex.overlap, ex._transport_req) == (8, False, "auto")


def test_fingerprint_distinguishes_async_knobs():
    base = FLConfig(strategy="fedavg", num_clients=4, num_models=4)
    f_host = engine_fingerprint(base)
    f_async = engine_fingerprint(dataclasses.replace(base, engine="async"))
    f_barrier = engine_fingerprint(
        dataclasses.replace(base, engine="async_barrier"))
    assert len({f_host, f_async, f_barrier}) == 3
    # and it is stable across calls (the checkpoint guard depends on it)
    assert f_async == engine_fingerprint(
        dataclasses.replace(base, engine="async"))


# --------------------------------------------------------------- AsyncSpec

def test_resolve_k_priority_and_clamping():
    assert AsyncSpec().resolve_k(7) == 7                       # barrier
    assert AsyncSpec(buffer_k=3).resolve_k(7) == 3
    assert AsyncSpec(buffer_k=30).resolve_k(7) == 7            # clamped
    assert AsyncSpec(buffer_frac=0.5).resolve_k(7) == 4        # round(3.5)
    assert AsyncSpec(buffer_k=2, buffer_frac=0.9).resolve_k(7) == 2
    assert AsyncSpec(buffer_frac=0.01).resolve_k(7) == 1       # >= 1


def test_discount_is_one_at_zero_staleness_and_decays():
    b = AsyncSpec(staleness_alpha=1.0, staleness_beta=0.5)
    assert b.discount(0) == 1.0
    assert b.discount(3) < b.discount(1) < b.discount(0)
    # beta=0 turns the discount off entirely
    off = AsyncSpec(staleness_beta=0.0)
    assert off.discount(10) == 1.0


def test_validate_rejects_bad_knobs():
    with pytest.raises(AssertionError):
        AsyncSpec(buffer_k=0).validate()
    with pytest.raises(AssertionError):
        AsyncSpec(buffer_frac=1.5).validate()
    with pytest.raises(AssertionError):
        EngineSpec(mode="warp").validate()


# --------------------------------------------------------------- RunResult

def _result():
    return RunResult(params={"w": 1}, ledger="LEDGER", history=RunHistory(
        accuracy=[0.1, 0.5, 0.9], loss=[2.0, 1.0, 0.5],
        diffusion_rounds=[1, 2, 1], iid_distance=[0.3, 0.2, 0.1],
        virtual_s=[1.0, 2.0, 4.0]))


def test_runresult_legacy_surface_and_unpacking():
    r = _result()
    assert r.final_params == r.params == {"w": 1}
    assert r.accuracy == [0.1, 0.5, 0.9]
    assert r.ledger == "LEDGER"
    params, ledger, history = r
    assert params == {"w": 1} and ledger == "LEDGER"
    assert isinstance(history, RunHistory)


def test_time_to_accuracy_uses_virtual_clock_when_present():
    r = _result()
    assert r.rounds_to_accuracy(0.5) == 2
    assert r.time_to_accuracy(0.5) == 2.0     # virtual_s[1]
    assert r.time_to_accuracy(0.99) is None
    sync = RunResult(params=None, ledger=None, history=RunHistory(
        accuracy=[0.1, 0.5], loss=[1, 1], diffusion_rounds=[0, 0],
        iid_distance=[0, 0]))
    assert sync.time_to_accuracy(0.5) == 2.0  # falls back to round index


def test_flresult_alias_is_runresult():
    from repro.fl.server import FLResult
    assert FLResult is RunResult
