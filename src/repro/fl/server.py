"""FL server runtime: FedDif (Algorithm 2) plus every comparison strategy of
Sec. VI — FedAvg [1], FedSwap [21] (full diffusion, no auction), STC [41]
(compressed uplink), TT-HF-like [22] (semi-decentralized cluster averaging),
D-PSGD-style gossip (fully decentralized; Appendix C Scenario 1), and a
``d2d_random_walk`` ablation (auction-free diffusion: models hop to random
feasible neighbours, isolating FedDif's *planning* gain from its *mobility*
gain on Table II's strategy axis).

The RoundSchedule / Executor seam
---------------------------------
``run_federated`` is the single entry point.  Each communication round runs
in three strategy-agnostic stages, mirroring the paper's PUCCH/PUSCH split:

1. **schedule** — ``repro.fl.schedulers.SCHEDULERS[cfg.strategy]`` turns the
   round's control-plane inputs (partition DSIs, wireless draw, QoS knobs)
   into a pure :class:`~repro.core.schedule.RoundSchedule`: slot-level
   train/permute/mix ops, wire events, aggregation weights.   [PUCCH]
2. **charge** — :func:`~repro.core.schedule.charge_schedule` replays the wire
   events into the :class:`ResourceLedger` (Sec. III-D metrics), identically
   for every executor.
3. **execute** — the executor selected by ``cfg.engine`` (an
   :class:`~repro.fl.engine.EngineSpec` or preset name) runs the ops:
   ``"host"`` on a per-slot pytree list (the reference semantics), ``"fleet"``
   on one client-stacked pytree via vmapped/jitted fedshard steps, and
   ``"sharded"`` with that client axis sharded over a ``("clients",)`` mesh
   (shard_map sessions, collective hops/aggregation — the large-N plane).
   When ``cfg.churn_rate > 0``, a per-round dropout mask is applied to the
   schedule first (``apply_round_churn``): dropped clients neither train nor
   carry aggregation weight, while their wire events still charge. [PUSCH]

Each round is a ``repro.obs`` span, ``fl.round``, holding one span per stage
(``fl.world``, ``fl.plan``, ``fl.charge``, ``fl.exec``, ``fl.eval``); they
record only inside ``obs.recording()`` or while a profiler trace is being
captured.

Adding a strategy therefore means: append its name to :data:`STRATEGIES` and
write one scheduler in ``repro.fl.schedulers`` — both executors, the ledger,
the experiment harness (``repro.fl.experiment``), the sweep registry
(``repro.experiments``) and the benchmarks pick it up by name with no
further plumbing.

The runtime is model-agnostic: pass any ``loss_fn(params, batch)`` +
``init_fn(key)`` + per-client batch iterators.

Control-plane determinism: when ``cfg.topology_seed`` is set, each round's
positions / channel draws come from a fresh ``default_rng([topology_seed, t])``
stream, decoupled from the model-init seed.  Diffusion plans then depend only
on (topology_seed, round, data partition, planner knobs), which lets a
:class:`~repro.core.diffusion.PlanCache` passed to ``run_federated`` replan
once per sweep cell and replay the plan across replicate seeds.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import numpy as np

from repro import obs
from repro.channels.fading import ChannelModel
from repro.channels.resources import (GAMMA_FLOOR, PRB_HZ, ResourceLedger,
                                      spectral_efficiency)
from repro.channels.topology import CellTopology
from repro.channels.world import SCENARIOS, HostWorld, per_client_energy_j
from repro.core import aggregation as agg
from repro.core.auction import AuctionConfig
from repro.core.diffusion import PLANNER_MODES, DiffusionPlanner, PlanCache
from repro.core.schedule import WireEvent, charge_schedule
from repro.fl.client import make_local_update
from repro.fl.engine import (EngineSpec, RunHistory, RunResult,
                             resolve_engine)
from repro.fl.executors import EXECUTORS, make_executor
from repro.fl.schedulers import (PROX_STRATEGIES, SCHEDULERS, RoundContext,
                                 apply_energy_cap, apply_round_churn)

Params = Any

__all__ = ["FLConfig", "FLResult", "RunResult", "EngineSpec",
           "run_federated", "STRATEGIES", "HOP_QUANTS"]

STRATEGIES = ("feddif", "fedavg", "fedswap", "stc", "tthf", "gossip",
              "feddif_stc", "fedprox", "feddif_prox", "d2d_random_walk")

HOP_QUANTS = ("none", "int8")


@dataclasses.dataclass
class FLConfig:
    strategy: str = "feddif"
    num_clients: int = 10
    num_models: int = 10               # M (FedDif trains M ≤ N models)
    rounds: int = 30                   # T communication rounds
    local_epochs: int = 1
    lr: float = 0.01
    momentum: float = 0.9
    batch_size: int = 16
    epsilon: float = 0.04              # min tolerable IID distance
    gamma_min: float = 1.0             # min tolerable QoS (bit/s/Hz)
    metric: str = "w1_norm"
    diffusion_ratio: float = 1.0       # fraction of PUEs allowed to diffuse
    stc_sparsity: float = 0.01
    prox_mu: float = 0.01              # FedProx proximal coefficient
    tthf_cluster_size: int = 5
    tthf_global_period: int = 4
    bits_per_param: int = 32
    seed: int = 0
    topology_seed: int | None = None   # decouple wireless draw from model seed
    random_walk_hops: int = 3          # hops/round for d2d_random_walk
    max_diffusion_rounds: int | None = None
    eval_every: int = 1
    churn_rate: float = 0.0          # per-round P(client drops out) — see
                                     # schedulers.apply_round_churn
    scenario: str = "static"         # wireless world evolution
                                     # (channels/world.SCENARIOS): "static" |
                                     # "mobile" (random waypoint) |
                                     # "multicell" (SINR handoff + inter-cell
                                     # interference) | "energy_capped"
                                     # (finite TX budgets).  "static" is
                                     # bit-identical to the pre-world runtime.
    uncertainty_weight: float = 0.0  # learning-value bid fusion weight w:
                                     # the planner's bids become
                                     # bids·(1 + w·value); 0.0 = off, the
                                     # exact pre-value auction
    energy_budget_j: float | None = None
                                     # per-client TX energy budget (J) when
                                     # scenario="energy_capped"; None = the
                                     # scenario default.  Depleted clients
                                     # drop out via churn semantics.
    allow_retraining: bool = False   # Appendix C-D (drops constraint 18c)
    underlay: bool = False           # Appendix C-F (D2D reuses CUE PRBs)
    checkpoint_every: int = 0        # durable round-state cadence R; 0 = off
                                     # (see repro.fl.resume.RoundCheckpointer)
    hop_quant: str = "none"          # D2D hop payload wire format: "none"
                                     # (fp32) | "int8" (per-row-block absmax
                                     # pack, kernels/quant.py).  Applies to
                                     # PermuteOp diffusion hops (feddif /
                                     # fedswap / d2d_random_walk); MixOp-
                                     # based exchanges (tthf, gossip) and
                                     # up/downlinks stay fp32.  Composes
                                     # numerically with feddif_stc, whose
                                     # ledger keeps the STC accounting.
    engine: "EngineSpec | str" = "host"
                                     # The engine selection
                                     # (repro.fl.engine): an EngineSpec, or
                                     # an ENGINE_PRESETS name ("host",
                                     # "fleet", "sharded", "auto", "async",
                                     # "async_barrier").  Picks the data
                                     # plane, the planner and the sharded /
                                     # async knobs.

# Legacy alias, one release: ``run_federated`` now returns the structured
# :class:`repro.fl.engine.RunResult` (params, ledger, history, engine), whose
# properties reproduce the old flat FLResult surface (``accuracy``, ``loss``,
# ``final_params``, ``round_wall_s``, ``rounds_to_accuracy``).
FLResult = RunResult


def _uplink_gamma(channel: ChannelModel, pos: np.ndarray,
                  rng: np.random.Generator) -> np.ndarray:
    """Spectral efficiency of each user's link to the BS at the origin."""
    d = np.linalg.norm(pos, axis=-1)
    gains = channel.sample_gains(np.maximum(d, 1.0), rng)
    return spectral_efficiency(channel.snr(gains))


def run_federated(init_fn: Callable, loss_fn: Callable,
                  client_batches: Sequence[Callable[[], list[dict]]],
                  dsi: np.ndarray, data_sizes: np.ndarray,
                  eval_fn: Callable[[Params], tuple[float, float]],
                  cfg: FLConfig,
                  plan_cache: PlanCache | None = None,
                  checkpointer=None, base_bits: float = 0.0,
                  value_fn: Callable[[Params], np.ndarray] | None = None
                  ) -> FLResult:
    """Run one FL experiment.

    Args:
      init_fn: key -> params.
      loss_fn: (params, batch) -> scalar.
      client_batches: per client, a callable returning one local epoch of
        batches.
      dsi / data_sizes: from the Dirichlet partitioner.
      eval_fn: params -> (accuracy, loss) on held-out data.
      cfg: experiment configuration; ``cfg.engine`` selects the data plane
        and the planner (see :func:`~repro.fl.engine.resolve_engine`).
      plan_cache: optional :class:`PlanCache` for FedDif strategies; only
        consulted when ``cfg.topology_seed`` is set (otherwise the wireless
        draw depends on ``cfg.seed`` and plans are not shareable).
      checkpointer: optional :class:`~repro.fl.resume.RoundCheckpointer`.
        When set, full round state is serialized every
        ``checkpointer.every`` rounds and, if a readable checkpoint exists
        in its directory, the loop resumes from it bit-identically.
      base_bits: serialized size of the frozen base under an adapter view
        (``repro.fl.adapters``).  Charged once as a round-0 downlink
        broadcast; 0.0 (full-params runs) charges nothing.
      value_fn: optional ``params -> (N,) learning value in [0, 1]``
        (``fl/experiment.py`` builds a predictive-uncertainty probe).  Only
        consulted when ``cfg.uncertainty_weight > 0``; the values fuse into
        the FedDif auction bids via ``kernels.ops.bid_value_fuse``.
    """
    assert cfg.strategy in STRATEGIES, cfg.strategy
    assert cfg.hop_quant in HOP_QUANTS, cfg.hop_quant
    assert cfg.scenario in SCENARIOS, cfg.scenario
    if cfg.num_models > cfg.num_clients:
        # The paper trains M ≤ N models (one PUE trains one model per round,
        # constraint 18d); the slot-per-client executors require it too.
        raise ValueError(
            f"num_models={cfg.num_models} > num_clients={cfg.num_clients}; "
            f"FedDif requires M ≤ N (set num_models <= num_clients)")
    # Engine resolution — the ONLY place an execution plane is selected.
    espec = resolve_engine(cfg)
    assert espec.planner in PLANNER_MODES, espec.planner
    if espec.mode == "async":
        from repro.fl.async_plane import run_buffered_async
        return run_buffered_async(init_fn, loss_fn, client_batches, dsi,
                                  data_sizes, eval_fn, cfg, espec,
                                  plan_cache=plan_cache,
                                  checkpointer=checkpointer,
                                  base_bits=base_bits, value_fn=value_fn)
    assert espec.mode in EXECUTORS, espec.mode
    n = cfg.num_clients
    rng = np.random.default_rng(cfg.seed)
    key = jax.random.PRNGKey(cfg.seed)
    topology = CellTopology(num_pues=n)
    channel = ChannelModel()
    auction = AuctionConfig(gamma_min=cfg.gamma_min, metric=cfg.metric,
                            allow_retraining=cfg.allow_retraining)
    planner = DiffusionPlanner(topology, channel, auction,
                               epsilon=cfg.epsilon,
                               max_rounds=cfg.max_diffusion_rounds,
                               underlay=cfg.underlay, mode=espec.planner)
    if cfg.strategy in PROX_STRATEGIES:
        # proximal local solver (anchor = the received model's weights)
        from repro.fl.fedprox import make_prox_local_update
        local_update = make_prox_local_update(loss_fn, cfg.prox_mu,
                                              cfg.momentum)
    else:
        local_update = make_local_update(loss_fn, cfg.momentum)
    executor = make_executor(espec.mode, loss_fn, local_update,
                             client_batches, cfg, espec)
    ledger = ResourceLedger()
    # The evolving wireless world.  Static consumes exactly the draws the
    # pre-world control plane did, so the whole run is bit-identical; the
    # other scenarios add mobility / handoff / energy on the same streams.
    world = HostWorld.create(cfg.scenario, topology, channel, n,
                             energy_budget_j=cfg.energy_budget_j)

    global_params = init_fn(key)
    model_bits = agg.model_bits(global_params, cfg.bits_per_param)
    # What one D2D hop actually moves: the int8-packed wire size under
    # hop_quant, the fp32 payload otherwise.  The auction prices hops
    # (Eq. 15) at this figure; up/downlinks keep charging model_bits.
    if cfg.hop_quant == "int8":
        from repro.fl.adapters import packed_bits
        hop_bits = packed_bits(global_params)
    else:
        hop_bits = model_bits
    auction.model_bits = hop_bits

    acc_hist, loss_hist, dif_hist, iid_hist = [], [], [], []
    round_wall: list[float] = []
    slots = None            # persistent per-slot state (gossip / tthf)
    start_t = 0

    if checkpointer is not None:
        state = checkpointer.restore(executor, global_params, cfg)
        if state is not None:
            start_t = state.step
            global_params = state.params
            slots = state.slots
            ledger = state.ledger
            acc_hist, loss_hist = state.acc_hist, state.loss_hist
            dif_hist, iid_hist = state.dif_hist, state.iid_hist
            round_wall = state.round_wall
            checkpointer.apply_rng_state(rng, state.rng_state)
            if start_t and cfg.topology_seed is not None:
                # Rebuild the world's round-t state: mobility / handoff
                # trajectories are pure functions of the per-round control
                # streams, which are independent of ``rng``, so replaying
                # them is exact.  (Per-client *energy* spent in replayed
                # rounds is not recharged — energy_capped runs should
                # checkpoint at rounds=cadence boundaries they can afford;
                # with topology_seed unset a mobile world restarts.)
                for tt in range(start_t):
                    world.advance_round(
                        np.random.default_rng([cfg.topology_seed, tt]))

    for t in range(start_t, cfg.rounds):
        with obs.fl_round(t):
            with obs.span("fl.world"):
                # Control-plane stream: per-round and model-seed-independent
                # when topology_seed is set, so diffusion plans are cacheable
                # across seeds.
                if cfg.topology_seed is not None:
                    ctrl_rng = np.random.default_rng([cfg.topology_seed, t])
                else:
                    ctrl_rng = rng
                pos = world.advance_round(ctrl_rng)
                up_gamma = np.maximum(world.uplink_gamma(ctrl_rng),
                                      GAMMA_FLOOR)
                interference = world.interference()
                learning_value = None
                if value_fn is not None and cfg.uncertainty_weight > 0.0:
                    learning_value = np.asarray(value_fn(global_params),
                                                np.float64)

            with obs.span("fl.plan"):
                ctx = RoundContext(cfg=cfg, t=t, dsi=dsi,
                                   data_sizes=data_sizes, pos=pos,
                                   rng=ctrl_rng, up_gamma=up_gamma,
                                   topology=topology, channel=channel,
                                   planner=planner, model_bits=model_bits,
                                   param_template=global_params,
                                   plan_cache=plan_cache, hop_bits=hop_bits,
                                   world=world, interference=interference,
                                   learning_value=learning_value)
                schedule = SCHEDULERS[cfg.strategy](ctx)
                if t == 0 and base_bits > 0.0:
                    # One-time frozen-base broadcast (adapter view): every
                    # round-t state derives from base + hopped adapter, so
                    # the base ships once on the round-0 downlink,
                    # strategy-independent.
                    schedule.wire.append(WireEvent(
                        "downlink", float(base_bits),
                        float(np.median(up_gamma)), n))
                schedule = apply_round_churn(ctx, schedule)
                if world.has_energy_cap:
                    schedule = apply_energy_cap(ctx, schedule,
                                                world.depleted())
            with obs.span("fl.charge"):
                charge_schedule(ledger, schedule)
                if world.has_energy_cap:
                    world.charge_energy(per_client_energy_j(schedule, n,
                                                            PRB_HZ))
            with obs.timed("fl.exec") as exec_span:
                global_params, slots = executor.run_round(
                    schedule, global_params, slots)
                jax.block_until_ready(global_params)
            round_wall.append(exec_span.seconds)
            dif_hist.append(schedule.diffusion_rounds)
            iid_hist.append(schedule.mean_iid)

            if (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1:
                with obs.span("fl.eval"):
                    a, l = eval_fn(global_params)
                acc_hist.append(float(a))
                loss_hist.append(float(l))

            if checkpointer is not None and checkpointer.due(t + 1,
                                                             cfg.rounds):
                checkpointer.save(t + 1, executor, global_params, slots,
                                  ledger, cfg, acc_hist=acc_hist,
                                  loss_hist=loss_hist, dif_hist=dif_hist,
                                  iid_hist=iid_hist, round_wall=round_wall,
                                  rng=rng)

    hist = RunHistory(accuracy=acc_hist, loss=loss_hist,
                      diffusion_rounds=dif_hist, iid_distance=iid_hist,
                      round_wall_s=round_wall)
    return RunResult(params=global_params, ledger=ledger, history=hist,
                     engine=espec, config=cfg)
