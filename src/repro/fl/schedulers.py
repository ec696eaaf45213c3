"""Per-strategy schedulers: one communication round -> :class:`RoundSchedule`.

Every Table-II strategy is a *scheduler* — a pure function from the round's
control-plane inputs (partition DSIs, wireless draw, QoS knobs) to a
:class:`~repro.core.schedule.RoundSchedule` — and nothing else.  Training and
parameter movement happen in an executor (``repro.fl.executors``), ledger
charging in :func:`~repro.core.schedule.charge_schedule`.  Adding a strategy
therefore means: write one ``schedule_*`` function, register it in
:data:`SCHEDULERS` — both executors, the ledger, the sweep registry and the
benchmarks pick it up with no further plumbing.

Determinism contract: a scheduler consumes ``ctx.rng`` in exactly the order
the paper's round would (positions → gains → matching draws), so host and
fleet executions of one config share one schedule, and plans stay cacheable
across replicate seeds (``FLConfig.topology_seed``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro import obs
from repro.channels.fading import ChannelModel
from repro.channels.resources import GAMMA_FLOOR, spectral_efficiency
from repro.channels.topology import CellTopology
from repro.core.diffusion import DiffusionPlanner, PlanCache, feddif_cache_key
from repro.core.dol import DiffusionState, iid_distance
from repro.core.schedule import (MixOp, PermuteOp, RoundSchedule, TrainOp,
                                 WireEvent, apply_churn,
                                 complete_round_permutation)
from repro.fl.compression import compressed_bits

__all__ = ["RoundContext", "SCHEDULERS", "PROX_STRATEGIES", "GAMMA_FLOOR",
           "apply_round_churn", "apply_energy_cap"]

# Strategies whose local solver is the FedProx proximal step.
PROX_STRATEGIES = ("fedprox", "feddif_prox")


@dataclasses.dataclass
class RoundContext:
    """Everything a scheduler may consult for one communication round ``t``.

    ``topology`` / ``channel`` / ``planner`` are built once per experiment in
    ``run_federated`` and shared by every round (the topology is *not*
    re-instantiated per strategy round).  ``param_template`` is the current
    global params, used only for *shapes* (compressed-bits accounting) —
    schedulers never read parameter values.
    """
    cfg: "FLConfig"                      # noqa: F821 — import cycle
    t: int
    dsi: np.ndarray
    data_sizes: np.ndarray
    pos: np.ndarray
    rng: np.random.Generator
    up_gamma: np.ndarray
    topology: CellTopology
    channel: ChannelModel
    planner: DiffusionPlanner
    model_bits: float
    param_template: object
    plan_cache: PlanCache | None = None
    # Per-hop D2D payload bits when the wire format differs from fp32
    # params (int8-packed adapter hops, FLConfig.hop_quant); None charges
    # model_bits.  Up/downlinks always charge model_bits.
    hop_bits: float | None = None
    # The round's wireless world (channels/world.HostWorld).  ``interference``
    # is its per-receiver co-channel power — scalar 0.0 outside multicell, so
    # the static SNR arithmetic is bit-identical to the pre-world path.
    world: object | None = None
    interference: np.ndarray | float = 0.0
    # Per-client learning value in [0, 1] (None when the signal is off);
    # fused into the FedDif bids with FLConfig.uncertainty_weight.
    learning_value: np.ndarray | None = None
    _dist: np.ndarray | None = dataclasses.field(default=None, repr=False)

    def d2d_bits(self) -> float:
        """Eq.-15 payload size S of one D2D hop under the active wire
        format (``repro.fl.adapters.packed_bits`` for int8 hops)."""
        return self.model_bits if self.hop_bits is None else self.hop_bits

    def pair_distances(self) -> np.ndarray:
        """(N, N) distance matrix for this round's positions, computed once
        (fedswap / random-walk draw gains many times per round over it)."""
        if self._dist is None:
            self._dist = self.topology.pairwise_distances(self.pos)
        return self._dist


def _mean_partition_iid(ctx: RoundContext) -> float:
    return float(np.mean(iid_distance(np.asarray(ctx.dsi), ctx.cfg.metric)))


def _downlink(ctx: RoundContext, bits: float | None = None) -> WireEvent:
    return WireEvent("downlink", ctx.model_bits if bits is None else bits,
                     float(np.median(ctx.up_gamma)), ctx.cfg.num_clients)


def _uplink(ctx: RoundContext, client: int,
            bits: float | None = None) -> WireEvent:
    return WireEvent("uplink", ctx.model_bits if bits is None else bits,
                     float(ctx.up_gamma[client]), src=int(client))


def _pair_gamma(ctx: RoundContext) -> np.ndarray:
    """One D2D channel draw over the round's positions (Sec. III-D).

    ``ctx.interference`` folds the world's per-receiver co-channel power
    into the SINR; its (n,) form broadcasts over the receiver (column)
    axis of the (n, n) link matrix."""
    gains = ctx.channel.sample_gains(ctx.pair_distances(), ctx.rng)
    return spectral_efficiency(ctx.channel.snr(gains, ctx.interference))


# Stream tag separating the churn draw from every other [seed, t] consumer.
_CHURN_STREAM = 0xC4


def apply_round_churn(ctx: RoundContext,
                      schedule: RoundSchedule) -> RoundSchedule:
    """Draw this round's churn/straggler mask and apply it to the schedule.

    Lives with the schedulers because it extends the determinism contract:
    the mask comes from a **dedicated** RNG stream keyed on
    ``[topology_seed (or seed), t, _CHURN_STREAM]`` — *not* from the tail
    of ``ctx.rng``, whose post-scheduler position depends on plan-cache
    hits and on the planner mode (a cache hit skips the channel draws a
    miss consumes).  A given config therefore drops the same clients in
    round ``t`` no matter which executor/planner/engine runs it or what
    the shared cache already contains; ``churn_rate=0`` draws nothing and
    existing trajectories are bit-identical.  Each client independently
    drops with probability ``FLConfig.churn_rate``; see
    :func:`~repro.core.schedule.apply_churn` for the dropped-client
    semantics (no training, zero aggregation weight, wire still charged).
    """
    rate = float(getattr(ctx.cfg, "churn_rate", 0.0))
    if rate <= 0.0:
        return schedule
    seed = (ctx.cfg.topology_seed if ctx.cfg.topology_seed is not None
            else ctx.cfg.seed)
    rng = np.random.default_rng([seed, ctx.t, _CHURN_STREAM])
    drop = rng.random(ctx.cfg.num_clients) < rate
    return apply_churn(schedule, drop)


def apply_energy_cap(ctx: RoundContext, schedule: RoundSchedule,
                     depleted: np.ndarray) -> RoundSchedule:
    """Drop clients whose TX-energy budget was spent in *prior* rounds.

    The ``energy_capped`` scenario's enforcement point: depletion reuses the
    churn semantics (:func:`~repro.core.schedule.apply_churn` — no training,
    zero aggregation weight, already-scheduled wire still charges, exactly
    like a battery dying mid-round).  The mask is deterministic (a pure
    function of past schedules), so no RNG stream is consumed and
    un-capped runs are untouched."""
    depleted = np.asarray(depleted, dtype=bool)
    if not depleted.any():
        return schedule
    return apply_churn(schedule, depleted)


# ----------------------------------------------------------------- schedulers

def schedule_fedavg(ctx: RoundContext) -> RoundSchedule:
    """FedAvg [1] (and FedProx [9] — same schedule, proximal local solver):
    broadcast, local update everywhere, weighted uplink aggregation."""
    n = ctx.cfg.num_clients
    wire = [_downlink(ctx)]
    wire += [_uplink(ctx, i) for i in range(n)]
    return RoundSchedule(
        num_slots=n,
        ops=[TrainOp(np.ones(n, dtype=bool))],
        wire=wire,
        agg=[(i, float(ctx.data_sizes[i])) for i in range(n)],
        mean_iid=_mean_partition_iid(ctx))


def schedule_stc(ctx: RoundContext) -> RoundSchedule:
    """STC [41]: full-model downlink, sparse-ternary-compressed delta uplink
    (Table II's compression baseline)."""
    n = ctx.cfg.num_clients
    up_bits = compressed_bits(ctx.param_template, ctx.cfg.stc_sparsity)
    wire = [_downlink(ctx)]
    wire += [_uplink(ctx, i, up_bits) for i in range(n)]
    return RoundSchedule(
        num_slots=n,
        ops=[TrainOp(np.ones(n, dtype=bool))],
        wire=wire,
        agg=[(i, float(ctx.data_sizes[i])) for i in range(n)],
        agg_mode="stc_delta",
        stc_sparsity=ctx.cfg.stc_sparsity,
        mean_iid=_mean_partition_iid(ctx))


def schedule_feddif(ctx: RoundContext) -> RoundSchedule:
    """FedDif (Algorithm 2): initial training by the holders, then the
    auction-planned diffusion rounds, then chain-weighted aggregation.
    ``feddif_stc`` ships STC-compressed deltas on every hop; ``feddif_prox``
    swaps the local solver (the schedule is identical)."""
    cfg = ctx.cfg
    n, m = cfg.num_clients, cfg.num_models
    compress = cfg.strategy == "feddif_stc"
    hop_bits = (compressed_bits(ctx.param_template, cfg.stc_sparsity)
                if compress else ctx.d2d_bits())

    with obs.span("fl.plan.state"):
        state = DiffusionState.init(m, n, ctx.dsi.shape[1])
        init_mask = np.zeros(n, dtype=bool)
        for mi in range(m):
            holder = int(state.holder[mi])
            init_mask[holder] = True
            state.record_training(mi, holder, ctx.dsi[holder],
                                  float(ctx.data_sizes[holder]))
    ops: list = [TrainOp(init_mask)]
    wire: list = [_downlink(ctx)]

    cache_key = None
    if ctx.plan_cache is not None and cfg.topology_seed is not None:
        cache_key = feddif_cache_key(cfg, ctx.t, ctx.dsi, ctx.data_sizes,
                                     ctx.d2d_bits(), ctx.planner.auction,
                                     values=ctx.learning_value)
    # World-model plan inputs: per-receiver interference (multicell), the
    # within-round WorldState + substep for mobile, and the learning-value
    # signal.  All default to the off/static values, keeping the pre-world
    # call bit-identical.
    planner_world = (ctx.world.planner_world()
                     if ctx.world is not None else None)
    step_m = (ctx.world.cfg.step_m
              if planner_world is not None else 0.0)
    plan = ctx.planner.plan_communication_round(
        state, ctx.dsi, ctx.data_sizes, ctx.rng, positions=ctx.pos,
        cache=ctx.plan_cache, cache_key=cache_key,
        interference=ctx.interference, values=ctx.learning_value,
        value_weight=float(getattr(cfg, "uncertainty_weight", 0.0)),
        world=planner_world, step_m=step_m)

    obs.count("fl.plan.hops", len(plan.hops))
    with obs.span("fl.plan.build"):
        slot_of_model = np.arange(m) % max(n, 1)
        for k in range(plan.num_rounds):
            hops = plan.hops_in_round(k)
            for h in hops:
                wire.append(WireEvent("d2d", hop_bits,
                                      max(h.gamma, GAMMA_FLOOR),
                                      src=int(h.src)))
            src_of_dst, mask, slot_of_model = complete_round_permutation(
                [(h.model, h.dst) for h in hops], slot_of_model, n)
            ops.append(PermuteOp(src_of_dst, mask, compress=compress))

        for mi in range(m):
            wire.append(_uplink(ctx, int(state.holder[mi])))
        return RoundSchedule(
            num_slots=n,
            ops=ops,
            wire=wire,
            agg=[(int(slot_of_model[mi]), float(state.chain_size[mi]))
                 for mi in range(m)],
            stc_sparsity=cfg.stc_sparsity,
            diffusion_rounds=plan.num_rounds,
            mean_iid=float(np.mean(plan.final_iid_distance)))


def schedule_fedswap(ctx: RoundContext) -> RoundSchedule:
    """FedSwap [21]: random full swaps until every model visited every PUE
    (full diffusion, no auction)."""
    cfg = ctx.cfg
    n = cfg.num_clients
    holder = np.arange(n)
    visited = np.eye(n, dtype=bool)
    slot_of_model = np.arange(n)
    ops: list = [TrainOp(np.ones(n, dtype=bool))]
    wire: list = [_downlink(ctx)]
    swaps = 0
    while not visited.all():
        perm = ctx.rng.permutation(n)
        gamma = _pair_gamma(ctx)
        hops, mask = [], np.zeros(n, dtype=bool)
        for mi in range(n):
            src, dst = int(holder[mi]), int(perm[mi])
            if src == dst:
                continue
            wire.append(WireEvent("d2d", ctx.d2d_bits(),
                                  max(float(gamma[src, dst]), GAMMA_FLOOR),
                                  src=src))
            holder[mi] = dst
            hops.append((mi, dst))
            if not visited[mi, dst]:
                mask[dst] = True
                visited[mi, dst] = True
        src_of_dst, _, slot_of_model = complete_round_permutation(
            hops, slot_of_model, n)
        ops.append(PermuteOp(src_of_dst, mask))
        swaps += 1
        if swaps > 4 * n:
            break
    for mi in range(n):
        wire.append(_uplink(ctx, int(holder[mi])))
    return RoundSchedule(
        num_slots=n,
        ops=ops,
        wire=wire,
        agg=[(int(slot_of_model[mi]), float(ctx.data_sizes[mi]))
             for mi in range(n)],
        diffusion_rounds=swaps)


def schedule_d2d_random_walk(ctx: RoundContext) -> RoundSchedule:
    """Auction-free diffusion ablation: models take random feasible D2D hops
    (same mobility as FedDif, zero planning — the Table-II gap to ``feddif``
    is what the auction buys).

    Host semantics allow several models on one PUE, so hops inside one walk
    round may collide on a destination; they are serialized into dst-unique
    *waves* (in model order) for the slot-bijection executors.
    """
    cfg = ctx.cfg
    n, m = cfg.num_clients, cfg.num_models
    holder = np.arange(m) % n
    visited = np.zeros((m, n), dtype=bool)
    init_mask = np.zeros(n, dtype=bool)
    for mi in range(m):
        h = int(holder[mi])
        init_mask[h] = True
        visited[mi, h] = True
    ops: list = [TrainOp(init_mask)]
    wire: list = [_downlink(ctx)]
    slot_of_model = np.arange(m) % max(n, 1)
    hops_done = 0
    for _ in range(cfg.random_walk_hops):
        gamma = _pair_gamma(ctx)
        round_hops: list[tuple[int, int]] = []
        for mi in range(m):
            src = int(holder[mi])
            cand = [j for j in range(n)
                    if j != src and not visited[mi, j]
                    and gamma[src, j] >= cfg.gamma_min]
            if not cand:
                continue
            dst = int(ctx.rng.choice(cand))
            wire.append(WireEvent("d2d", ctx.d2d_bits(),
                                  max(float(gamma[src, dst]), GAMMA_FLOOR),
                                  src=src))
            holder[mi] = dst
            visited[mi, dst] = True
            round_hops.append((mi, dst))
        if not round_hops:
            break
        hops_done += 1
        # Serialize dst collisions into waves, preserving model order.
        waves: list[list[tuple[int, int]]] = []
        for model, dst in round_hops:
            for wave in waves:
                if all(d != dst for _, d in wave):
                    wave.append((model, dst))
                    break
            else:
                waves.append([(model, dst)])
        for wave in waves:
            src_of_dst, mask, slot_of_model = complete_round_permutation(
                wave, slot_of_model, n)
            ops.append(PermuteOp(src_of_dst, mask))
    for mi in range(m):
        wire.append(_uplink(ctx, int(holder[mi])))
    # Chain weights and DoL follow Eq. (2): each model's mixture of the DSIs
    # it visited, weighted by client data size.
    sizes = np.asarray(ctx.data_sizes, np.float64)
    chain_sizes = visited @ sizes
    dol = (visited * sizes[None, :]) @ np.asarray(ctx.dsi)
    dol = dol / np.maximum(chain_sizes[:, None], 1e-9)
    return RoundSchedule(
        num_slots=n,
        ops=ops,
        wire=wire,
        agg=[(int(slot_of_model[mi]), float(chain_sizes[mi]))
             for mi in range(m)],
        diffusion_rounds=hops_done,
        mean_iid=float(np.mean(np.asarray(
            iid_distance(dol, cfg.metric)))))


def schedule_tthf(ctx: RoundContext) -> RoundSchedule:
    """TT-HF-like [22]: local updates + intra-cluster D2D consensus each
    round; global aggregation (uplink + broadcast reset) only every
    ``tthf_global_period`` rounds."""
    cfg = ctx.cfg
    n, cs = cfg.num_clients, cfg.tthf_cluster_size
    clusters = [list(range(i, min(i + cs, n))) for i in range(0, n, cs)]
    gamma = _pair_gamma(ctx)
    ops: list = [TrainOp(np.ones(n, dtype=bool))]
    wire: list = []
    groups = []
    for cl in clusters:
        head = cl[0]
        for i in cl[1:]:
            wire.append(WireEvent("d2d", ctx.model_bits,
                                  max(float(gamma[i, head]), GAMMA_FLOOR),
                                  src=i))
        groups.append((tuple(cl), tuple(float(ctx.data_sizes[i])
                                        for i in cl)))
    ops.append(MixOp(tuple(groups)))
    if (ctx.t + 1) % cfg.tthf_global_period == 0:
        for cl in clusters:
            wire.append(_uplink(ctx, cl[0]))
        wire.append(_downlink(ctx))
        ops.append(MixOp(((tuple(range(n)),
                           tuple(float(s) for s in ctx.data_sizes)),)))
    return RoundSchedule(
        num_slots=n,
        ops=ops,
        wire=wire,
        agg=[(i, float(ctx.data_sizes[i])) for i in range(n)],
        persistent=True)


def schedule_gossip(ctx: RoundContext) -> RoundSchedule:
    """D-PSGD-style gossip (Appendix C Scenario 1): train locally, average
    with one random neighbour over D2D — fully decentralized, no BS."""
    cfg = ctx.cfg
    n = cfg.num_clients
    gamma = _pair_gamma(ctx)
    perm = ctx.rng.permutation(n)
    wire: list = []
    groups = []
    for a in range(0, n - 1, 2):
        i, j = int(perm[a]), int(perm[a + 1])
        wire.append(WireEvent("d2d", ctx.model_bits,
                              max(float(gamma[i, j]), GAMMA_FLOOR), src=i))
        wire.append(WireEvent("d2d", ctx.model_bits,
                              max(float(gamma[j, i]), GAMMA_FLOOR), src=j))
        groups.append(((i, j), (float(ctx.data_sizes[i]),
                                float(ctx.data_sizes[j]))))
    return RoundSchedule(
        num_slots=n,
        ops=[TrainOp(np.ones(n, dtype=bool)), MixOp(tuple(groups))],
        wire=wire,
        agg=[(i, float(ctx.data_sizes[i])) for i in range(n)],
        persistent=True,
        diffusion_rounds=1)


SCHEDULERS: dict[str, Callable[[RoundContext], RoundSchedule]] = {
    "feddif": schedule_feddif,
    "feddif_stc": schedule_feddif,
    "feddif_prox": schedule_feddif,
    "fedavg": schedule_fedavg,
    "fedprox": schedule_fedavg,
    "stc": schedule_stc,
    "fedswap": schedule_fedswap,
    "tthf": schedule_tthf,
    "gossip": schedule_gossip,
    "d2d_random_walk": schedule_d2d_random_walk,
}
