"""Buffered-async (FedBuff-style) round plane — the event-driven engine.

The sync planes are bulk-synchronous: every round is a barrier, so one
straggler stalls the fleet.  This module replays the **same**
:class:`~repro.core.schedule.RoundSchedule` IR through a deterministic
event queue instead:

1. **Dispatch.**  Each server tick ``t`` builds its round exactly like
   ``run_federated`` — same control-plane RNG streams, same scheduler, same
   churn, same ledger charging — then annotates the schedule with arrival
   times (:func:`~repro.core.schedule.annotate_arrivals`).  Per-slot compute
   durations come from data sizes x a lognormal per-round jitter x the
   client's persistent speed; D2D hop and uplink link times come from the
   **jnp channel twins** (Rayleigh gains → SNR → Eq.-14 spectral efficiency
   → seconds = bits / (γ · PRB_HZ)), keyed by ``fold_in``-derived PRNG
   streams so every draw is a pure function of ``(seed, t)`` — resumed runs
   redraw identical delays with no stored RNG position.
2. **Park.**  Diffusion hops whose payload would reach the carrier after
   ``AsyncSpec.hop_deadline_s`` are parked: the carrier keeps the late
   model but skips its training session, while the hop's wire events stay
   charged (Eq. 15) — stale airtime is still airtime.
3. **Buffer.**  The round's op work runs on an inner sync data plane
   (``HostExecutor`` or ``FleetExecutor`` via the ``run_ops``/``aggregate``
   split), and each aggregation contribution is pushed into a min-heap
   keyed ``(arrival_time, seq)``.
4. **Tick.**  The server aggregates the first **K** arrivals
   (``AsyncSpec.resolve_k``) with staleness-discounted weights
   ``w · alpha / (1 + s)^beta`` where ``s`` = ticks since the contribution
   was issued; the tick's virtual clock advances to the K-th arrival.
   Contributions older than ``max_staleness`` are dropped unaggregated.
   After the last dispatch round, drain ticks flush the remaining buffer.

**Degeneracy contract** (pinned by ``tests/test_async_plane.py``): with
K = everything, a zero delay model, and the discount off, every tick pops
the round's contributions in issue order with unit discount, so the
aggregation is the *same* ``agg.fedavg`` call the sync ``host`` executor
makes — params, ledger, and histories are bit-identical.

In front sits the population sampler (``AsyncSpec.population > 0``): each
tick draws its cohort of ``num_clients`` users from a simulated population
(:class:`~repro.fl.population.Population`), mapping users onto the
Dirichlet data shards — ``num_clients`` becomes cohort size, not world
size.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.channels.fading import ChannelModel
from repro.channels.resources import (GAMMA_FLOOR, PRB_HZ, ResourceLedger,
                                      spectral_efficiency_jax)
from repro.channels.topology import CellTopology
from repro.core import aggregation as agg
from repro.core.auction import AuctionConfig
from repro.core.diffusion import DiffusionPlanner, PlanCache
from repro.core.schedule import (ArrivalModel, WireEvent, annotate_arrivals,
                                 charge_schedule)
from repro.fl.client import make_local_update
from repro.fl.engine import AsyncSpec, EngineSpec, RunHistory, RunResult
from repro.fl.executors import make_executor
from repro.fl.population import Population
from repro.fl.schedulers import (PROX_STRATEGIES, SCHEDULERS, RoundContext,
                                 apply_round_churn)

Params = Any

__all__ = ["run_buffered_async", "ASYNC_COMPATIBLE_AGG"]

# Strategies the buffered-async plane can execute: non-persistent rounds
# aggregating raw params.  Persistent slot state (gossip / tthf) and
# stc_delta uplinks tie the aggregate to one barrier's slot snapshot — a
# buffered re-ordering has no meaning for them.
ASYNC_COMPATIBLE_AGG = "params"

# PRNG stream tags (folded into the per-round key) separating the async
# delay draws from each other; the numpy control plane uses its own
# [seed, t, tag] streams (churn 0xC4, population 0xA7).
_STREAM_COMPUTE = 1
_STREAM_D2D = 2


@dataclasses.dataclass(order=True)
class _Contribution:
    """One buffered aggregation contribution, heap-ordered by arrival."""
    arrival_s: float
    seq: int
    round: int = dataclasses.field(compare=False)
    slot: int = dataclasses.field(compare=False)
    weight: float = dataclasses.field(compare=False)
    tree: Any = dataclasses.field(compare=False, repr=False)


def _arrival_model(b: AsyncSpec, seed: int, t: int, pos: np.ndarray,
                   up_gamma: np.ndarray, channel: ChannelModel,
                   data_rows: np.ndarray, speed: np.ndarray,
                   hop_bits: float, model_bits: float,
                   interference: np.ndarray | float = 0.0) -> ArrivalModel:
    """Draw round ``t``'s delay world from the jnp channel twins.

    Pure in ``(seed, t)``: the key is ``fold_in(PRNGKey(seed), t)``, so the
    same round redraws the same delays across runs and across ``--resume``.
    ``delay_scale == 0`` short-circuits to the zero model (the sync-
    degenerate configuration) without consuming any keys.
    """
    n = len(pos)
    if b.delay_scale <= 0.0:
        return ArrivalModel.zeros(n)
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(t))
    # Compute: rows x delay_scale seconds at unit speed, lognormal-jittered
    # per client per round, divided by the client's persistent speed.
    kz = jax.random.fold_in(key, _STREAM_COMPUTE)
    z = jax.random.normal(kz, (n,))
    sig = float(b.delay_sigma)
    jitter = jnp.exp(sig * z - 0.5 * sig * sig)
    train_s = (float(b.delay_scale) * jnp.asarray(data_rows, jnp.float32)
               * jitter / jnp.asarray(speed, jnp.float32))
    # Links: one Rayleigh draw over this round's geometry (Eq. 12 → Eq. 14),
    # seconds = payload bits / (γ · PRB_HZ) on one PRB.
    kd = jax.random.fold_in(key, _STREAM_D2D)
    dist = CellTopology.pairwise_distances_jax(
        jnp.asarray(pos, jnp.float32))
    gains = channel.sample_gains_jax(kd, jnp.maximum(dist, 1.0))
    # World interference enters the delay SINR exactly as it enters the
    # scheduler's rate SINR: per-receiver power broadcast over columns.
    # (Passed through unconverted: the scalar-0.0 static case must follow
    # the exact arithmetic of the pre-world default argument.)
    gamma_d2d = jnp.maximum(
        spectral_efficiency_jax(channel.snr_jax(gains, interference)),
        GAMMA_FLOOR)
    hop_s = float(hop_bits) / (gamma_d2d * PRB_HZ)
    uplink_s = float(model_bits) / (np.asarray(up_gamma, np.float64)
                                    * PRB_HZ)
    return ArrivalModel(train_s=np.asarray(train_s, np.float64),
                        hop_s=np.asarray(hop_s, np.float64),
                        uplink_s=np.asarray(uplink_s, np.float64))


def _discounted_fedavg(popped: list[_Contribution], tick: int,
                       b: AsyncSpec) -> tuple[Params, float]:
    """Aggregate one tick's arrivals with staleness-discounted weights.

    Weight normalization happens inside :func:`agg.fedavg` (float64 sum →
    float32 division), so discounted weights always renormalize to 1 —
    the property ``tests/test_async_plane.py`` pins.  Returns the new
    global and the tick's mean staleness.
    """
    staleness = [max(0, tick - c.round) for c in popped]
    weights = [c.weight * b.discount(s)
               for c, s in zip(popped, staleness)]
    if not sum(weights) > 0.0:
        # Zero-row Dirichlet shards train in zero seconds, so they can fill
        # an entire K-arrival tick with zero-weight contributions (the sync
        # barrier never sees this: it always aggregates the full cohort,
        # where they add exactly 0 to the Eq.-11 sums).  Leave the global
        # unchanged — bitwise what these contributions would contribute.
        return None, float(np.mean(staleness))
    trees = [c.tree for c in popped]
    return agg.fedavg(trees, weights), float(np.mean(staleness))


def run_buffered_async(init_fn: Callable, loss_fn: Callable,
                       client_batches: Sequence[Callable],
                       dsi: np.ndarray, data_sizes: np.ndarray,
                       eval_fn: Callable, cfg, espec: EngineSpec,
                       plan_cache: PlanCache | None = None,
                       checkpointer=None,
                       base_bits: float = 0.0,
                       value_fn: Callable | None = None) -> RunResult:
    """Event-driven counterpart of ``run_federated``'s round loop.

    Called by ``run_federated`` when the resolved engine mode is
    ``"async"`` — same arguments plus the resolved :class:`EngineSpec`.
    """
    from repro.channels.world import HostWorld, per_client_energy_j
    from repro.fl.server import STRATEGIES
    from repro.fl.schedulers import apply_energy_cap

    b = espec.buffered
    assert cfg.strategy in STRATEGIES, cfg.strategy
    n = int(cfg.num_clients)
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
        from repro.fl.fedprox import make_prox_local_update
        local_update = make_prox_local_update(loss_fn, cfg.prox_mu,
                                              cfg.momentum)
    else:
        local_update = make_local_update(loss_fn, cfg.momentum)
    # Same evolving world as the sync loop — the async plane's arrival
    # model reads its interference so delay SINRs and rate SINRs agree.
    world = HostWorld.create(getattr(cfg, "scenario", "static"), topology,
                             channel, n,
                             energy_budget_j=getattr(cfg, "energy_budget_j",
                                                     None))

    # Control-plane seed for delay/cohort draws: the topology seed when set
    # (plan-cache sharing across replicate seeds then stays valid — every
    # seed sees the same cohorts and delays), the model seed otherwise.
    ctrl_seed = (cfg.topology_seed if cfg.topology_seed is not None
                 else cfg.seed)

    # Population front end: slot c of the inner executor draws whatever
    # data shard the tick's cohort assigned it, through one mutable
    # indirection the per-slot batch closures read at call time.
    pop = None
    cohort = np.arange(n, dtype=np.int64)
    if b.population > 0:
        num_shards = len(client_batches)
        pop = Population(int(b.population), num_shards, seed=int(ctrl_seed),
                         avail_alpha=b.avail_alpha, avail_beta=b.avail_beta,
                         speed_sigma=b.speed_sigma)
        batches_view = [
            (lambda c=c: client_batches[int(cohort[c])]())
            for c in range(n)]
    else:
        batches_view = list(client_batches[:n])

    inner_name = espec.inner_data_plane(n)
    inner = make_executor(inner_name, loss_fn, local_update, batches_view,
                          cfg, espec)
    ledger = ResourceLedger()
    global_params = init_fn(key)
    model_bits = agg.model_bits(global_params, cfg.bits_per_param)
    if cfg.hop_quant == "int8":
        from repro.fl.adapters import packed_bits
        hop_bits = packed_bits(global_params)
    else:
        hop_bits = model_bits
    auction.model_bits = hop_bits

    hist = RunHistory()
    pending: list[_Contribution] = []
    seq = 0
    vtime = 0.0
    start_t = 0

    if checkpointer is not None:
        state = checkpointer.restore(inner, global_params, cfg)
        if state is not None:
            start_t = state.step
            global_params = state.params
            ledger = state.ledger
            hist = RunHistory(
                accuracy=state.acc_hist, loss=state.loss_hist,
                diffusion_rounds=state.dif_hist,
                iid_distance=state.iid_hist,
                round_wall_s=state.round_wall,
                **(state.async_hist or {}))
            checkpointer.apply_rng_state(rng, state.rng_state)
            vtime = float(state.buffer_meta["virtual_s"])
            seq = int(state.buffer_meta["next_seq"])
            pending = _unpack_buffer(state.buffer_tree, state.buffer_meta)
            heapq.heapify(pending)
            # Replay the world up to the restored round (same per-round RNG
            # streams as the live run, so mobile positions resume exactly).
            if cfg.topology_seed is not None:
                for tt in range(start_t):
                    world.advance_round(
                        np.random.default_rng([cfg.topology_seed, tt]))

    def eval_due(t: int) -> bool:
        return (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1

    def server_tick(t: int, num_new: int) -> None:
        """Aggregate the first K arrivals; advance the virtual clock."""
        nonlocal global_params, vtime
        if not pending:
            return
        k = b.resolve_k(num_new if num_new > 0 else len(pending))
        k = min(k, len(pending))
        popped: list[_Contribution] = []
        dropped = 0
        while pending and len(popped) < k:
            c = heapq.heappop(pending)
            if b.max_staleness is not None \
                    and t - c.round > b.max_staleness:
                dropped += 1
                continue
            popped.append(c)
        if not popped:
            return
        vtime = max(vtime, popped[-1].arrival_s)
        new_params, mean_stale = _discounted_fedavg(popped, t, b)
        if new_params is not None:
            global_params = new_params
        hist.virtual_s.append(float(vtime))
        hist.arrivals.append(len(popped))
        hist.staleness.append(mean_stale)

    for t in range(start_t, cfg.rounds):
        t_exec = time.time()
        if pop is not None:
            draw = pop.sample_cohort(t, n)
            cohort[:] = draw.shards
            speed = draw.speed
        else:
            speed = np.ones(n)
        dsi_t = np.asarray(dsi)[cohort]
        sizes_t = np.asarray(data_sizes)[cohort]

        # --- control plane: identical streams to the sync loop -----------
        if cfg.topology_seed is not None:
            ctrl_rng = np.random.default_rng([cfg.topology_seed, t])
        else:
            ctrl_rng = rng
        pos = world.advance_round(ctrl_rng)
        up_gamma = np.maximum(world.uplink_gamma(ctrl_rng), GAMMA_FLOOR)
        learning_value = None
        if value_fn is not None \
                and getattr(cfg, "uncertainty_weight", 0.0) > 0.0:
            learning_value = np.asarray(value_fn(global_params), np.float64)
        ctx = RoundContext(cfg=cfg, t=t, dsi=dsi_t, data_sizes=sizes_t,
                           pos=pos, rng=ctrl_rng, up_gamma=up_gamma,
                           topology=topology, channel=channel,
                           planner=planner, model_bits=model_bits,
                           param_template=global_params,
                           plan_cache=plan_cache, hop_bits=hop_bits,
                           world=world, interference=world.interference(),
                           learning_value=learning_value)
        schedule = SCHEDULERS[cfg.strategy](ctx)
        if schedule.persistent or schedule.agg_mode != ASYNC_COMPATIBLE_AGG:
            raise ValueError(
                f"strategy {cfg.strategy!r} needs persistent slot state or "
                f"agg_mode={schedule.agg_mode!r}; the buffered-async engine "
                f"supports non-persistent params-aggregation strategies "
                f"(feddif / fedavg / fedswap / d2d_random_walk / prox "
                f"variants) — run it on a sync engine instead")
        if t == 0 and base_bits > 0.0:
            schedule.wire.append(WireEvent("downlink", float(base_bits),
                                           float(np.median(up_gamma)), n))
        schedule = apply_round_churn(ctx, schedule)
        if world.has_energy_cap:
            schedule = apply_energy_cap(ctx, schedule, world.depleted())

        # --- arrival annotation + Eq.-15 charging ------------------------
        model = _arrival_model(b, ctrl_seed, t, pos, up_gamma, channel,
                               sizes_t, speed, hop_bits, model_bits,
                               interference=world.interference())
        schedule, arrival_s, parked = annotate_arrivals(
            schedule, model, hop_deadline_s=b.hop_deadline_s)
        charge_schedule(ledger, schedule)
        if world.has_energy_cap:
            world.charge_energy(per_client_energy_j(schedule, n, PRB_HZ))

        # --- dispatch: inner op replay, contributions into the heap ------
        slots = inner.run_ops(schedule, global_params, None)
        for slot, w in schedule.agg:
            heapq.heappush(pending, _Contribution(
                arrival_s=vtime + float(arrival_s[slot]), seq=seq,
                round=t, slot=int(slot), weight=float(w),
                tree=inner.slot_state(slots, int(slot))))
            seq += 1

        # --- server tick -------------------------------------------------
        server_tick(t, num_new=len(schedule.agg))
        jax.block_until_ready(global_params)
        hist.round_wall_s.append(time.time() - t_exec)
        hist.diffusion_rounds.append(schedule.diffusion_rounds)
        hist.iid_distance.append(schedule.mean_iid)
        hist.parked_hops.append(parked)

        if eval_due(t):
            a, l = eval_fn(global_params)
            hist.accuracy.append(float(a))
            hist.loss.append(float(l))

        if checkpointer is not None and checkpointer.due(t + 1, cfg.rounds):
            btree, bmeta = _pack_buffer(pending, vtime, seq)
            checkpointer.save(
                t + 1, inner, global_params, None, ledger, cfg,
                acc_hist=hist.accuracy, loss_hist=hist.loss,
                dif_hist=hist.diffusion_rounds, iid_hist=hist.iid_distance,
                round_wall=hist.round_wall_s, rng=rng,
                async_hist={"virtual_s": hist.virtual_s,
                            "arrivals": hist.arrivals,
                            "staleness": hist.staleness,
                            "parked_hops": hist.parked_hops},
                buffer_tree=btree, buffer_meta=bmeta)

    # Drain: flush contributions still buffered after the last dispatch
    # round — K at a time, evaluating after each tick so the curves keep
    # tracking the virtual clock.  Empty immediately in the degenerate
    # (barrier) configuration.
    t = cfg.rounds
    while pending:
        server_tick(t, num_new=0)
        a, l = eval_fn(global_params)
        hist.accuracy.append(float(a))
        hist.loss.append(float(l))
        t += 1

    return RunResult(params=global_params, ledger=ledger, history=hist,
                     engine=espec, config=cfg)


# ------------------------------------------------------------------ buffer
# serialization — the mid-tick state the commit-marker protocol must cover.

def _pack_buffer(pending: list[_Contribution], vtime: float, next_seq: int
                 ) -> tuple[Any, dict]:
    """Stack the pending contributions into one leading-axis pytree (the
    npz payload) plus a JSON-able meta dict.  Heap order is recovered on
    restore from the (arrival, seq) keys."""
    entries = sorted(pending)
    meta = {"count": len(entries),
            "virtual_s": float(vtime),
            "next_seq": int(next_seq),
            "arrival_s": [float(c.arrival_s) for c in entries],
            "seq": [int(c.seq) for c in entries],
            "round": [int(c.round) for c in entries],
            "slot": [int(c.slot) for c in entries],
            "weight": [float(c.weight) for c in entries]}
    if not entries:
        return None, meta
    host = [jax.device_get(c.tree) for c in entries]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *host)
    return stacked, meta


def _unpack_buffer(buffer_tree: Any, meta: dict) -> list[_Contribution]:
    count = int(meta.get("count", 0))
    if count == 0:
        return []
    out = []
    for i in range(count):
        tree = jax.tree.map(lambda x: jnp.asarray(x[i]), buffer_tree)
        out.append(_Contribution(
            arrival_s=float(meta["arrival_s"][i]), seq=int(meta["seq"][i]),
            round=int(meta["round"][i]), slot=int(meta["slot"][i]),
            weight=float(meta["weight"][i]), tree=tree))
    return out
