"""Diffusion-round planner — the control plane of Algorithm 2 (lines 14–26).

``plan_communication_round`` runs the DoL-broadcast → bid → auction →
schedule loop until the halting condition ``W1(ψ, U) ≤ ε`` holds for every
model (or no feasible pair remains), producing a :class:`DiffusionPlan`:
the per-diffusion-round list of (model, src PUE, dst PUE, γ, bandwidth).

The plan is *pure scheduling* — no training happens here.  The FL runtime
(``repro.fl.server``) executes a plan by running local updates and parameter
transfers (host mode) or ppermute collectives (SPMD mode), and the launcher
replays plans on the production mesh.  This mirrors the paper's split between
PUCCH control signalling and PUSCH model transmission.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict

import numpy as np

from repro.channels.fading import ChannelModel
from repro.channels.resources import spectral_efficiency
from repro.channels.topology import CellTopology
from repro.core import dol as dol_lib
from repro.core.auction import AuctionConfig, run_auction

__all__ = ["DiffusionHop", "DiffusionPlan", "DiffusionPlanner", "PlanCache",
           "plan_cache_key", "feddif_cache_key", "PLANNER_MODES"]

PLANNER_MODES = ("host", "jax")


@dataclasses.dataclass
class DiffusionHop:
    model: int
    src: int
    dst: int
    gamma: float            # spectral efficiency of the scheduled link
    bandwidth: float        # Eq. 15 cost (Hz·s)
    decrement: float        # δ (Eq. 17)
    round_index: int


@dataclasses.dataclass
class DiffusionPlan:
    hops: list[DiffusionHop]
    num_rounds: int
    final_iid_distance: np.ndarray      # (M,)
    efficiency_per_round: list[float]
    num_models: int | None = None       # M — set by the planner

    def hops_in_round(self, k: int) -> list[DiffusionHop]:
        return [h for h in self.hops if h.round_index == k]

    def as_permutations(self, num_clients: int,
                        num_models: int | None = None
                        ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per-round (permutation, train_mask) for the SPMD ppermute path.

        The auction's matching is *partial* (some models stay put), but
        ``jax.lax.ppermute`` needs a bijection over client slots.
        :func:`repro.core.schedule.complete_round_permutation` completes the
        partial mapping src→dst to a permutation (unscheduled sources stay
        put where possible, displaced idle models are "parked" on free
        slots); ``train_mask`` marks the slots whose freshly received model
        performs a local update, i.e. the scheduled dsts.

        ``num_models`` is the fleet size M; models that never hop still own
        a slot, so inferring M from the hop list would silently drop them
        from the parking bookkeeping.  Defaults to the plan's recorded M
        (falling back to hop-list inference only for plans from external
        sources that predate the field).

        perm[k][c] = slot that receives slot c's buffer in round k.
        """
        from repro.core.schedule import complete_round_permutation
        if num_models is None:
            num_models = self.num_models
        if num_models is None:
            num_models = (max(h.model for h in self.hops) + 1
                          if self.hops else 0)
        slot_of_model = np.arange(num_models) % max(num_clients, 1)
        out = []
        for k in range(self.num_rounds):
            hops = [(h.model, h.dst) for h in self.hops_in_round(k)]
            src_of_dst, mask, slot_of_model = complete_round_permutation(
                hops, slot_of_model, num_clients)
            out.append((np.argsort(src_of_dst), mask))
        return out


def plan_cache_key(topology_seed: int, round_index: int, dsi: np.ndarray,
                   data_sizes: np.ndarray, epsilon: float, gamma_min: float,
                   metric: str, extra: tuple = ()) -> tuple:
    """Cache key for one communication round's :class:`DiffusionPlan`.

    A plan is a pure function of the control-plane inputs: the topology /
    channel draw (derived from ``(topology_seed, round_index)``), the client
    DSIs and data sizes (fixed by the data seed), and the planner knobs
    (ε, γ_min, metric, …).  It is *independent of the model-init seed*, which
    is what makes multi-seed replication cacheable: the orchestrator replans
    once per sweep cell and replays the plan for every replicate seed.
    """
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(dsi, np.float32).tobytes())
    h.update(np.ascontiguousarray(data_sizes, np.float64).tobytes())
    return (int(topology_seed), int(round_index), float(epsilon),
            float(gamma_min), str(metric), h.hexdigest(), tuple(extra))


def feddif_cache_key(cfg, t: int, dsi: np.ndarray, data_sizes: np.ndarray,
                     model_bits: float, auction: AuctionConfig,
                     values: np.ndarray | None = None) -> tuple:
    """The one :func:`plan_cache_key` builder for FedDif call sites.

    ``cfg`` is the experiment's ``FLConfig`` (duck-typed to avoid the import
    cycle).  Folds in every plan input: the sizing knobs, the full
    :class:`AuctionConfig` surface (incl. ``outage_max`` and
    ``bandwidth_budget``, which alter feasibility/FCFS), the world scenario
    and learning-value weight, and the resolved engine's planner mode (host
    and jax plans are parity-checked but not bit-guaranteed, so they never
    share a cache line).  The schedulers and the sweep pre-planner both
    call this helper — hand-built ``extra=`` tuples cannot drift apart.

    ``values`` is the round's learning-value vector.  It depends on the
    model parameters — i.e. on the *model-init seed* — so when the value
    signal is active its digest joins the key and plans stop being
    shareable across replicate seeds (the pre-planner skips those cells).
    """
    from repro.fl.engine import resolve_engine   # fl imports core
    vdigest = ""
    if values is not None and getattr(cfg, "uncertainty_weight", 0.0):
        vdigest = hashlib.sha1(
            np.ascontiguousarray(values, np.float32).tobytes()).hexdigest()
    return plan_cache_key(
        cfg.topology_seed, t, dsi, data_sizes, cfg.epsilon, cfg.gamma_min,
        cfg.metric,
        extra=(cfg.num_clients, cfg.num_models, float(model_bits),
               cfg.max_diffusion_rounds, cfg.allow_retraining, cfg.underlay,
               float(auction.outage_max), float(auction.bandwidth_budget),
               resolve_engine(cfg).planner,
               getattr(cfg, "scenario", "static"),
               float(getattr(cfg, "uncertainty_weight", 0.0)), vdigest))


class PlanCache:
    """LRU memo of ``(DiffusionPlan, post-plan DiffusionState)`` snapshots.

    ``DiffusionPlanner.plan_communication_round`` consults it when given a
    ``cache_key``: on a hit the stored plan is returned and the caller's
    mutable :class:`~repro.core.dol.DiffusionState` is fast-forwarded to the
    stored post-plan snapshot — the auction / bidding loop (the expensive
    host-side control plane) is skipped entirely.  Keys come from
    :func:`plan_cache_key`; see there for what makes two rounds equivalent.
    """

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._store: OrderedDict[tuple, tuple] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: tuple) -> bool:
        """Presence probe that does not touch hit/miss counters or LRU
        order (used by the sweep pre-planner to skip planned rounds)."""
        return key in self._store

    def lookup(self, key: tuple):
        """Return ``(plan, post_state)`` or ``None``; counts hits/misses."""
        entry = self._store.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._store.move_to_end(key)
        self.hits += 1
        return entry

    def store(self, key: tuple, plan: "DiffusionPlan",
              post_state: dol_lib.DiffusionState) -> None:
        self._store[key] = (plan, post_state.snapshot())
        self._store.move_to_end(key)
        while len(self._store) > self.max_entries:
            self._store.popitem(last=False)

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._store)}

    # ------------------------------------------------------- serialization
    #
    # Durable sweeps persist the cache across preemptions so a resumed run
    # *replays* plans instead of replanning them.  The state dict is pure
    # JSON-able data (no pickle): keys are nested tuples of scalars (lists
    # on disk, retupled on load), plans/hops/states are plain number lists.

    def state_dict(self) -> dict:
        entries = []
        for key, (plan, state) in self._store.items():
            entries.append({
                "key": _key_jsonable(key),
                "plan": {
                    "hops": [[h.model, h.src, h.dst, h.gamma, h.bandwidth,
                              h.decrement, h.round_index]
                             for h in plan.hops],
                    "num_rounds": int(plan.num_rounds),
                    "final_iid_distance":
                        np.asarray(plan.final_iid_distance,
                                   np.float32).tolist(),
                    "efficiency_per_round":
                        [float(e) for e in plan.efficiency_per_round],
                    "num_models": plan.num_models,
                },
                "state": {
                    "dol": np.asarray(state.dol, np.float32).tolist(),
                    "chain_size":
                        np.asarray(state.chain_size, np.float32).tolist(),
                    "visited": np.asarray(state.visited, bool).tolist(),
                    "holder": np.asarray(state.holder, np.int64).tolist(),
                    "round_index": int(state.round_index),
                },
            })
        return {"version": 1, "max_entries": self.max_entries,
                "hits": self.hits, "misses": self.misses,
                "entries": entries}

    def load_state_dict(self, state: dict) -> None:
        """Merge serialized entries into this cache (counters adopted too,
        so a resumed sweep's cache statistics continue, not restart)."""
        self.max_entries = int(state.get("max_entries", self.max_entries))
        self.hits = int(state.get("hits", 0))
        self.misses = int(state.get("misses", 0))
        for e in state["entries"]:
            key = _key_from_jsonable(e["key"])
            p, s = e["plan"], e["state"]
            plan = DiffusionPlan(
                hops=[DiffusionHop(model=int(h[0]), src=int(h[1]),
                                   dst=int(h[2]), gamma=float(h[3]),
                                   bandwidth=float(h[4]),
                                   decrement=float(h[5]),
                                   round_index=int(h[6]))
                      for h in p["hops"]],
                num_rounds=int(p["num_rounds"]),
                final_iid_distance=np.asarray(p["final_iid_distance"],
                                              np.float32),
                efficiency_per_round=[float(x)
                                      for x in p["efficiency_per_round"]],
                num_models=(None if p["num_models"] is None
                            else int(p["num_models"])))
            post = dol_lib.DiffusionState(
                dol=np.asarray(s["dol"], np.float32),
                chain_size=np.asarray(s["chain_size"], np.float32),
                visited=np.asarray(s["visited"], bool),
                holder=np.asarray(s["holder"], np.int64),
                round_index=int(s["round_index"]))
            self._store[key] = (plan, post)
            self._store.move_to_end(key)
        while len(self._store) > self.max_entries:
            self._store.popitem(last=False)

    @classmethod
    def from_state_dict(cls, state: dict) -> "PlanCache":
        cache = cls(max_entries=int(state.get("max_entries", 256)))
        cache.load_state_dict(state)
        return cache


def _key_jsonable(key):
    """Cache keys are nested tuples of (int, float, bool, str, None) — JSON
    keeps every scalar type distinct, only the tuple/list shape changes."""
    if isinstance(key, tuple):
        return [_key_jsonable(k) for k in key]
    return key


def _key_from_jsonable(key):
    if isinstance(key, list):
        return tuple(_key_from_jsonable(k) for k in key)
    return key


class DiffusionPlanner:
    """Plans all diffusion rounds of one communication round."""

    def __init__(self, topology: CellTopology | None = None,
                 channel: ChannelModel | None = None,
                 auction: AuctionConfig | None = None,
                 epsilon: float = 0.04,
                 max_rounds: int | None = None,
                 underlay: bool = False,
                 mode: str = "host"):
        assert mode in PLANNER_MODES, mode
        if mode == "jax" and underlay:
            raise ValueError("planner mode 'jax' does not model underlay "
                             "CUE interference (Appendix C-F); use 'host'")
        self.topology = topology or CellTopology()
        self.channel = channel or ChannelModel()
        self.auction = auction or AuctionConfig()
        self.epsilon = epsilon          # minimum tolerable IID distance
        self.max_rounds = max_rounds
        self.underlay = underlay        # Appendix C-F: D2D reuses CUE PRBs
        self.mode = mode                # "host" oracle | "jax" device plane

    def plan_communication_round(
            self, state: dol_lib.DiffusionState, dsi: np.ndarray,
            data_sizes: np.ndarray, rng: np.random.Generator,
            positions: np.ndarray | None = None,
            cache: PlanCache | None = None,
            cache_key: tuple | None = None,
            interference: np.ndarray | float = 0.0,
            values: np.ndarray | None = None,
            value_weight: float = 0.0,
            world=None, step_m: float = 0.0) -> DiffusionPlan:
        """Runs auctions until halting; mutates ``state`` with visited sets.

        When ``cache``/``cache_key`` are given (see :func:`plan_cache_key`),
        a hit skips the whole auction loop: the cached plan is returned and
        ``state`` is fast-forwarded to the cached post-plan snapshot.  The
        caller is responsible for a key that captures every plan input.

        ``interference`` is the world's per-receiver co-channel power
        (multicell SINR — frozen within the round); ``values`` /
        ``value_weight`` fuse the learning-value signal into the bids;
        ``world`` + ``step_m`` (mobile scenario) step a random-waypoint
        WorldState one deterministic substep per diffusion round, moving
        every link's pathloss under the auction as the paper's Eqs. 12–14
        would see it.  All default off — the static path is bit-identical
        to the pre-world planner.

        With ``mode='jax'`` the same contract is served by the jitted
        device planner (:mod:`repro.core.planner`): identical hop lists on
        the same channel draws, but the draws are pre-sampled ``max_rounds``
        deep, so the *post-plan position* of ``rng`` differs from the lazy
        host loop's.
        """
        if self.mode == "jax":
            from repro.core.planner import plan_communication_round_jax
            return plan_communication_round_jax(
                self, state, dsi, data_sizes, rng, positions=positions,
                cache=cache, cache_key=cache_key,
                interference=interference, values=values,
                value_weight=value_weight, world=world, step_m=step_m)
        if cache is not None and cache_key is not None:
            entry = cache.lookup(cache_key)
            if entry is not None:
                plan, post_state = entry
                state.restore(post_state)
                return plan
        n = dsi.shape[0]
        pos = way = None
        if world is not None:
            pos = np.asarray(world.positions, np.float64)
            way = np.asarray(world.waypoints, np.float64)
            positions = pos
        elif positions is None:
            positions = self.topology.sample_positions(rng, n)
        dist = self.topology.pairwise_distances(positions)
        beta = 10 ** (self.channel.large_scale_db(dist) / 10.0)
        mean_snr = self.channel.snr(beta, interference)  # Rayleigh power
        #                                                  marginalized

        hops: list[DiffusionHop] = []
        eff_hist: list[float] = []
        # Worst case O(N_P(N_P-1)) rounds (Sec. V-D); each PUE trains each
        # model at most once, so N_P rounds suffice when all M hop per round.
        max_rounds = self.max_rounds or n * (n - 1)
        k = 0
        while k < max_rounds:
            iid = state.iid_distances(self.auction.metric)
            active = iid > self.epsilon
            if not self.auction.allow_retraining:
                # Models at chain length N visited everyone (full diffusion).
                active &= ~state.visited.all(axis=1)
            if not active.any():
                break
            if world is not None:
                # Host mirror of the planner-loop world step (mobile).
                delta = way - pos
                d = np.linalg.norm(delta, axis=-1, keepdims=True)
                frac = np.minimum(step_m, d) / np.maximum(d, 1e-9)
                pos = pos + delta * frac
                dist = self.topology.pairwise_distances(pos)
                beta = 10 ** (self.channel.large_scale_db(dist) / 10.0)
                mean_snr = self.channel.snr(beta, interference)
            gains = self.channel.sample_gains(dist, rng)
            cue_interference = 0.0
            if self.underlay:
                n_cues = rng.poisson(self.topology.cue_rate)
                cue_interference = self.channel.sample_cue_interference(
                    rng, n_cues, self.topology.radius_m)
            snr = self.channel.snr(gains, interference + cue_interference)
            result = run_auction(state, dsi, data_sizes, gains, mean_snr,
                                 snr, self.auction, values=values,
                                 value_weight=value_weight)
            # Only schedule hops for still-active models.
            scheduled = [(m, i) for m, i in result.pairs if active[m]]
            if not scheduled:
                break
            k += 1
            gamma = spectral_efficiency(snr)
            for m, i in scheduled:
                src = int(state.holder[m])
                hops.append(DiffusionHop(
                    model=m, src=src, dst=i,
                    gamma=float(gamma[src, i]),
                    bandwidth=result.bandwidth[m],
                    decrement=result.decrements[m],
                    round_index=k - 1))
                state.record_training(m, i, dsi[i], float(data_sizes[i]))
            eff_hist.append(result.efficiency)
        state.round_index += k
        plan = DiffusionPlan(hops=hops, num_rounds=k,
                             final_iid_distance=state.iid_distances(
                                 self.auction.metric),
                             efficiency_per_round=eff_hist,
                             num_models=int(state.dol.shape[0]))
        if cache is not None and cache_key is not None:
            cache.store(cache_key, plan, state)
        return plan
