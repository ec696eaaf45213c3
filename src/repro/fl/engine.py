"""EngineSpec / RunResult — the typed engine-selection and result API.

Engine selection used to be string sprawl across ``FLConfig``: ``executor``
(+ the sharded plane's ``shard_overlap`` / ``shard_hop_transport`` /
``shard_microbatch`` / ``mesh_model_axis``), ``planner``, and the
orchestrator's ``_pick_executor`` heuristic on top.  The buffered-async
plane (PR 9) would have added a fourth ad-hoc knob family.  This module
collapses all of it into one frozen :class:`EngineSpec`:

* ``EngineSpec`` is the **single selection authority**: every runtime entry
  point (``run_federated``, the sweep orchestrator, the benches) resolves
  its engine through :func:`resolve_engine` and nothing else constructs an
  engine from raw strings.
* Legacy ``FLConfig`` string kwargs keep working through
  :meth:`EngineSpec.from_config` — a deprecation shim that warns **once**
  per process and maps the old fields onto a spec.
* :meth:`EngineSpec.auto` absorbs ``orchestrator._pick_executor``: the
  measured sharded/fleet crossover lives here, next to the thing it picks.
* Named :data:`ENGINE_PRESETS` ("host", "fleet", "sharded", "async", …) are
  what ``launch/sweep --engine`` and ``benchmarks/run.py --engine`` accept,
  and what ``FLConfig.engine`` stores when given a string.

:class:`RunResult` is the structured return of ``run_federated``: params,
ledger, a :class:`RunHistory` of per-round curves, and the engine actually
used.  The legacy ``FLResult`` flat attributes (``accuracy``, ``loss``,
``final_params``, …) are preserved as properties, and positional unpacking
``params, ledger, history = result`` works via ``__iter__`` for one release.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

__all__ = ["AsyncSpec", "EngineSpec", "ENGINE_PRESETS", "resolve_engine",
           "engine_fingerprint", "RunHistory", "RunResult",
           "SHARDED_CROSSOVER_N"]

# Measured fleet/sharded crossover (benchmarks/run.py fleet_scaling on the
# 2-device CPU mesh): below this N the collective rendezvous overhead of the
# sharded plane exceeds its parallelism win.  EngineSpec.auto() downgrades
# sharded requests under it — the heuristic formerly in
# ``orchestrator._pick_executor``.
SHARDED_CROSSOVER_N = 64

#: Execution planes run_federated can dispatch to.
ENGINE_MODES = ("host", "fleet", "sharded", "async", "auto")


@dataclasses.dataclass(frozen=True)
class AsyncSpec:
    """Knobs of the buffered-async (FedBuff-style) round plane.

    The **defaults are degenerate on purpose**: ``buffer_k=None`` +
    ``buffer_frac=None`` aggregates every arrival of the round (a barrier),
    ``delay_scale=0`` makes every arrival instantaneous, and
    ``staleness_beta=0`` turns the discount off — so
    ``EngineSpec(mode="async")`` with stock knobs reproduces the sync
    ``host`` executor bit-identically (the degeneracy contract
    ``tests/test_async_plane.py`` pins).

    Attributes:
      buffer_k: aggregate the first K arrivals per server tick.  ``None``
        defers to ``buffer_frac``; both ``None`` means K = all of the
        round's contributions (sync barrier).
      buffer_frac: K as a fraction of the round's contribution count
        (``K = max(1, round(frac * M))``); only read when ``buffer_k`` is
        ``None``.
      staleness_alpha / staleness_beta: the FedBuff-style discount applied
        to a contribution aggregated ``s`` server ticks after it was
        issued: ``alpha / (1 + s) ** beta``.  ``beta=0`` disables it
        (``alpha`` then scales all weights uniformly and cancels in the
        normalized Eq.-11 mean).
      max_staleness: drop (never aggregate) contributions older than this
        many ticks; ``None`` keeps everything buffered.
      delay_scale: seconds of local-training time per data row at unit
        client speed.  ``0.0`` disables the whole delay model — compute
        *and* link delays are exactly zero and every round's arrivals are
        simultaneous.
      delay_sigma: sigma of the lognormal per-client compute jitter
        (``exp(sigma * Z)``, Z ~ N(0,1) per client per round).
      hop_deadline_s: park diffusion hops whose payload would arrive at
        the carrier later than this (the stale carrier still receives the
        model — it just skips the training session; the wire event stays
        charged, Eq. 15).  ``None`` never parks.
      population: size of the simulated user population the cohort is drawn
        from each tick (``fl/population.py``).  ``0`` disables sampling —
        ``num_clients`` is the world size, as in the sync planes.  When
        set, ``num_clients`` becomes the *cohort* size.
      avail_alpha / avail_beta: Beta-distribution shape of per-user
        availability (the sampling weight) across the population.
      speed_sigma: sigma of the *persistent* lognormal per-user compute
        speed across the population (heterogeneous hardware); drawn once
        per user, not per round.
    """
    buffer_k: int | None = None
    buffer_frac: float | None = None
    staleness_alpha: float = 1.0
    staleness_beta: float = 0.0
    max_staleness: int | None = None
    delay_scale: float = 0.0
    delay_sigma: float = 0.0
    hop_deadline_s: float | None = None
    population: int = 0
    avail_alpha: float = 2.0
    avail_beta: float = 2.0
    speed_sigma: float = 0.5

    def discount(self, staleness) -> float:
        """Staleness weight multiplier ``alpha / (1 + s) ** beta``."""
        return float(self.staleness_alpha
                     / (1.0 + float(staleness)) ** self.staleness_beta)

    def resolve_k(self, num_contributions: int) -> int:
        """K for a tick with ``num_contributions`` fresh contributions."""
        if self.buffer_k is not None:
            return max(1, min(int(self.buffer_k), num_contributions))
        if self.buffer_frac is not None:
            return max(1, min(int(round(self.buffer_frac
                                        * num_contributions)),
                              num_contributions))
        return num_contributions

    def validate(self) -> None:
        assert self.buffer_k is None or self.buffer_k >= 1, self.buffer_k
        assert self.buffer_frac is None or 0.0 < self.buffer_frac <= 1.0, \
            self.buffer_frac
        assert self.staleness_alpha > 0.0, self.staleness_alpha
        assert self.staleness_beta >= 0.0, self.staleness_beta
        assert self.delay_scale >= 0.0, self.delay_scale
        assert self.population >= 0, self.population


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """The typed engine selection — everything that picks an execution plane.

    Attributes:
      mode: "host" | "fleet" | "sharded" | "async" | "auto" ("auto" resolves
        by fleet size and device count, see :meth:`auto`).
      planner: "host" | "jax" control plane (``core.diffusion``).
      data_plane: the async plane's *inner* op executor ("auto" | "host" |
        "fleet") — the buffered-async engine replays each round's schedule
        ops through it, then re-orders the aggregation by arrival.
      shard_overlap / shard_hop_transport / shard_microbatch /
        mesh_model_axis: the sharded plane's knobs, verbatim from the old
        ``FLConfig`` fields.
      buffered: the :class:`AsyncSpec` knobs (read when ``mode="async"``).
    """
    mode: str = "host"
    planner: str = "host"
    data_plane: str = "auto"
    shard_overlap: str = "auto"
    shard_hop_transport: str = "auto"
    shard_microbatch: int = 32
    mesh_model_axis: int = 1
    buffered: AsyncSpec = dataclasses.field(default_factory=AsyncSpec)

    # --------------------------------------------------------- validation

    def validate(self) -> None:
        assert self.mode in ENGINE_MODES, self.mode
        assert self.planner in ("host", "jax"), self.planner
        assert self.data_plane in ("auto", "host", "fleet"), self.data_plane
        assert self.shard_overlap in ("auto", "on", "off"), self.shard_overlap
        assert self.shard_hop_transport in ("auto", "ring", "gather"), \
            self.shard_hop_transport
        self.buffered.validate()

    # --------------------------------------------------------- resolution

    def auto(self, num_clients: int) -> "EngineSpec":
        """Resolve "auto" and downgrade infeasible sharded requests.

        Absorbs ``orchestrator._pick_executor``: a sharded engine below the
        measured :data:`SHARDED_CROSSOVER_N` (or on a single device, where
        the mesh degenerates anyway) downgrades to the fleet plane;
        ``mode="auto"`` picks sharded above the crossover on a multi-device
        runtime and fleet otherwise.  Idempotent; never changes an explicit
        host/fleet/async request.
        """
        import jax
        mode = self.mode
        multi = jax.device_count() > 1
        if mode == "auto":
            mode = ("sharded" if multi and num_clients >= SHARDED_CROSSOVER_N
                    else "fleet")
        if mode == "sharded" and num_clients < SHARDED_CROSSOVER_N:
            mode = "fleet"
        return self if mode == self.mode \
            else dataclasses.replace(self, mode=mode)

    def inner_data_plane(self, num_clients: int) -> str:
        """The async plane's inner op executor, "auto" resolved by size."""
        if self.data_plane != "auto":
            return self.data_plane
        return "fleet" if num_clients >= SHARDED_CROSSOVER_N else "host"

    def describe(self) -> str:
        """Stable one-line fingerprint (checkpoint config guard, records)."""
        b = self.buffered
        base = (f"{self.mode}/planner={self.planner}"
                f"/overlap={self.shard_overlap}"
                f"/transport={self.shard_hop_transport}"
                f"/mb={self.shard_microbatch}/km={self.mesh_model_axis}")
        if self.mode != "async":
            return base
        return (base + f"/data={self.data_plane}/k={b.buffer_k}"
                f"/frac={b.buffer_frac}/a={b.staleness_alpha}"
                f"/b={b.staleness_beta}/smax={b.max_staleness}"
                f"/ds={b.delay_scale}/sig={b.delay_sigma}"
                f"/ddl={b.hop_deadline_s}/pop={b.population}"
                f"/av={b.avail_alpha},{b.avail_beta}"
                f"/spd={b.speed_sigma}")

    # ------------------------------------------------------ legacy mapping

    @classmethod
    def from_config(cls, cfg) -> "EngineSpec":
        """Deprecation shim: map the legacy ``FLConfig`` string kwargs onto
        a spec.  Warns once per process when any legacy engine field is
        set away from its default (the new spelling is
        ``FLConfig(engine=EngineSpec(...))`` or a preset name)."""
        spec = cls(mode=str(getattr(cfg, "executor", "host")),
                   planner=str(getattr(cfg, "planner", "host")),
                   shard_overlap=str(getattr(cfg, "shard_overlap", "auto")),
                   shard_hop_transport=str(getattr(cfg, "shard_hop_transport",
                                                   "auto")),
                   shard_microbatch=int(getattr(cfg, "shard_microbatch", 32)),
                   mesh_model_axis=int(getattr(cfg, "mesh_model_axis", 1)))
        global _WARNED_LEGACY
        if not _WARNED_LEGACY and spec != cls():
            _WARNED_LEGACY = True
            warnings.warn(
                "engine selection via FLConfig string kwargs (executor=, "
                "planner=, shard_*=) is deprecated; pass "
                "FLConfig(engine=EngineSpec(...)) or a preset name "
                "(engine='fleet') instead — the legacy kwargs keep working "
                "for one release through this shim",
                DeprecationWarning, stacklevel=3)
        return spec

    @classmethod
    def preset(cls, name: str) -> "EngineSpec":
        try:
            return ENGINE_PRESETS[name]
        except KeyError:
            raise ValueError(
                f"unknown engine preset {name!r}; expected one of "
                f"{sorted(ENGINE_PRESETS)}") from None


#: Named engine presets — what ``--engine`` flags and ``FLConfig.engine``
#: strings resolve to.  "async" is the headline buffered-async
#: configuration: half-buffer ticks, staleness discount on, lognormal
#: compute stragglers and channel-drawn link delays.
ENGINE_PRESETS: dict[str, EngineSpec] = {
    "host": EngineSpec(mode="host"),
    "fleet": EngineSpec(mode="fleet"),
    "sharded": EngineSpec(mode="sharded"),
    "auto": EngineSpec(mode="auto"),
    "async": EngineSpec(mode="async", buffered=AsyncSpec(
        buffer_frac=0.5, staleness_beta=0.5,
        delay_scale=0.01, delay_sigma=1.0)),
    # Barrier-on-the-event-queue: the async machinery with K = everything
    # and the same delay model — the sync comparison arm of fig_async /
    # the async_throughput bench (tick time = slowest arrival).
    "async_barrier": EngineSpec(mode="async", buffered=AsyncSpec(
        delay_scale=0.01, delay_sigma=1.0)),
}

_WARNED_LEGACY = False


def resolve_engine(cfg) -> EngineSpec:
    """THE engine-selection authority: ``FLConfig`` -> :class:`EngineSpec`.

    ``cfg.engine`` wins when set (an :class:`EngineSpec`, or a preset name);
    otherwise the legacy string kwargs map through the deprecation shim.
    ``mode="auto"`` resolves against ``cfg.num_clients``.
    """
    eng = getattr(cfg, "engine", None)
    if eng is None:
        spec = EngineSpec.from_config(cfg)
    elif isinstance(eng, str):
        spec = EngineSpec.preset(eng)
    elif isinstance(eng, EngineSpec):
        spec = eng
    else:
        raise TypeError(f"FLConfig.engine must be an EngineSpec or a preset "
                        f"name, got {type(eng).__name__}")
    if spec.mode == "auto":
        spec = spec.auto(int(getattr(cfg, "num_clients", 0)))
    spec.validate()
    return spec


def engine_fingerprint(cfg) -> str:
    """Resolved-engine fingerprint for the checkpoint config guard."""
    return resolve_engine(cfg).describe()


# --------------------------------------------------------------------------
# RunResult — the structured return of run_federated
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RunHistory:
    """Per-round curves of one run.  The async plane fills the last four."""
    accuracy: list = dataclasses.field(default_factory=list)
    loss: list = dataclasses.field(default_factory=list)
    diffusion_rounds: list = dataclasses.field(default_factory=list)
    iid_distance: list = dataclasses.field(default_factory=list)
    round_wall_s: list = dataclasses.field(default_factory=list)
    # --- async round plane only (empty under the sync engines) ---
    virtual_s: list = dataclasses.field(default_factory=list)   # tick clock
    arrivals: list = dataclasses.field(default_factory=list)    # agg'd per tick
    staleness: list = dataclasses.field(default_factory=list)   # mean per tick
    parked_hops: list = dataclasses.field(default_factory=list)  # per round


@dataclasses.dataclass
class RunResult:
    """What ``run_federated`` returns: the structured (params, ledger,
    history) triple plus the engine actually used.

    Backwards compatibility (one release): the flat ``FLResult`` attributes
    are properties over ``history``, and ``params, ledger, history = result``
    unpacks via ``__iter__``.
    """
    params: Any
    ledger: Any
    history: RunHistory
    engine: EngineSpec | None = None
    config: Any = None

    def __iter__(self):
        yield self.params
        yield self.ledger
        yield self.history

    # ------------------------------------------- legacy FLResult surface

    @property
    def final_params(self):
        return self.params

    @property
    def accuracy(self) -> list:
        return self.history.accuracy

    @property
    def loss(self) -> list:
        return self.history.loss

    @property
    def diffusion_rounds(self) -> list:
        return self.history.diffusion_rounds

    @property
    def iid_distance(self) -> list:
        return self.history.iid_distance

    @property
    def round_wall_s(self) -> list:
        return self.history.round_wall_s

    def rounds_to_accuracy(self, target: float) -> int | None:
        for i, a in enumerate(self.history.accuracy):
            if a >= target:
                return i + 1
        return None

    def time_to_accuracy(self, target: float) -> float | None:
        """Virtual seconds to reach ``target`` accuracy (async plane; falls
        back to the round index when no virtual clock was recorded)."""
        r = self.rounds_to_accuracy(target)
        if r is None:
            return None
        if self.history.virtual_s:
            return float(self.history.virtual_s[min(
                r - 1, len(self.history.virtual_s) - 1)])
        return float(r)

    @classmethod
    def from_histories(cls, *, accuracy, loss, ledger, diffusion_rounds,
                       iid_distance, config=None, final_params=None,
                       round_wall_s=(), engine=None,
                       **async_hist) -> "RunResult":
        """Build a result from the flat legacy field spelling (replication
        engines, tests)."""
        hist = RunHistory(accuracy=list(accuracy), loss=list(loss),
                          diffusion_rounds=list(diffusion_rounds),
                          iid_distance=list(iid_distance),
                          round_wall_s=list(round_wall_s), **async_hist)
        return cls(params=final_params, ledger=ledger, history=hist,
                   engine=engine, config=config)
