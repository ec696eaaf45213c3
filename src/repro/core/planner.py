"""Device-resident diffusion planner — the jitted/batched twin of
:meth:`repro.core.diffusion.DiffusionPlanner.plan_communication_round`.

The host planner runs Algorithm 1/2's bid → auction → schedule loop as a
Python ``while`` with an O(n³) Hungarian per diffusion round.  This module
ports the whole loop to JAX:

* the round loop is a ``lax.while_loop`` over an immutable
  :class:`~repro.core.dol.PlannerState` with **fixed-shape padded hop
  buffers** (``max_rounds`` static), so one compilation serves every round;
* the matching is the Bertsekas ε-scaling **auction**
  (:func:`repro.core.matching.auction_assign`) — parallelizable,
  ``while_loop``-shaped, and literally the paper's auction mechanism
  (Sec. V / Eq. 38);
* the whole round planner ``vmap``s over a leading batch axis, so a sweep
  orchestrator can plan *every cell × communication round of a sweep in one
  device call* and pre-populate the :class:`~repro.core.diffusion.PlanCache`
  (see :func:`repro.experiments.orchestrator.prepopulate_plan_cache`).

Parity contract: both planner modes consume the *same host-drawn channel
realizations* (``draw_gamma_sequence`` pre-draws ``max_rounds`` Rayleigh
rounds from the caller's ``numpy`` Generator in exactly the order the lazy
host loop would), and the arithmetic mirrors the host oracle op-for-op, so
the decoded hop lists (model, src, dst, round) coincide with the host
planner's — asserted by ``tests/test_planner_jax.py`` and the
``planner_speedup`` benchmark.  A fully device-resident draw
(:func:`device_gamma_sequence`, explicit PRNG key) is available when host
parity is not required.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.channels.resources import (outage_probability_jax,
                                      required_bandwidth_jax,
                                      spectral_efficiency,
                                      spectral_efficiency_jax)
from repro.core import dol as dol_lib
from repro.core.dol import PlannerState
from repro.core.matching import auction_assign
from repro.kernels import ops as kernel_ops

__all__ = ["PlanInputs", "PlanOutputs", "draw_gamma_sequence",
           "draw_fading_sequence", "device_gamma_sequence",
           "plan_round_inputs", "plan_rounds", "plan_rounds_batched",
           "decode_plan", "plan_communication_round_jax"]


class PlanInputs(NamedTuple):
    """Per-cell planner inputs — a flat array pytree, stackable over cells.

    ``epsilon`` … ``model_bits`` are traced scalars (not statics), so one
    compiled planner serves a whole sweep grid over ε / γ_min / α / tasks;
    only shapes and the distance metric specialize the compilation.
    """
    dol0: jax.Array          # (M, C) post-initial-training DoLs
    chain_size0: jax.Array   # (M,)
    visited0: jax.Array      # (M, N) bool
    holder0: jax.Array       # (M,) int32
    dsi: jax.Array           # (N, C)
    data_sizes: jax.Array    # (N,)
    gamma_seq: jax.Array     # (R, N, N) per-round spectral efficiency —
                             # reinterpreted as the raw Exp(1) Rayleigh
                             # powers |h|² when the mobile world recomputes
                             # γ from stepped positions inside the loop
    mean_snr: jax.Array      # (N, N) large-scale-only SNR (Eq. 39 outage)
    epsilon: jax.Array       # () halting tolerance
    gamma_min: jax.Array     # () constraint (18e)
    outage_max: jax.Array    # () Eq. (39) cap
    bandwidth_budget: jax.Array  # () constraint (18f)
    model_bits: jax.Array    # () S in Eq. (15)
    # Optional trailing fields (None keeps the pre-world pytree structure
    # and therefore the pre-world compiled traces).
    value: jax.Array | None = None         # (N,) learning value in [0, 1]
    value_weight: jax.Array | None = None  # () fusion weight w
    world: object | None = None            # WorldState (mobile scenario)
    chan: jax.Array | None = None          # (4,) [p/σ², β₀dB, κ, d₀] for
                                           # in-loop Eq. 12–14 (mobile)


class PlanOutputs(NamedTuple):
    """Padded plan tensors for one cell: row k of each (R, M) buffer holds
    diffusion round k, valid where ``scheduled[k]`` (and k < num_rounds)."""
    num_rounds: jax.Array    # () int32
    dst: jax.Array           # (R, M) int32
    scheduled: jax.Array     # (R, M) bool
    src: jax.Array           # (R, M) int32
    gamma: jax.Array         # (R, M) link spectral efficiency of the hop
    bandwidth: jax.Array     # (R, M) Eq. 15 cost
    decrement: jax.Array     # (R, M) δ (Eq. 17)
    weight: jax.Array        # (R, M) Eq. 36 edge weight (hop ordering)
    efficiency: jax.Array    # (R,) E(i*, B*) per round (Eq. 16)
    state: PlannerState      # post-plan diffusion state
    final_iid: jax.Array     # (M,)
    converged: jax.Array     # () bool — False if any used auction hit its
                             # iteration cap (plan may be truncated)


def _plan_rounds(inp: PlanInputs, *, metric: str, allow_retraining: bool,
                 mobility: bool = False, step_m: float = 0.0,
                 use_value: bool = False) -> PlanOutputs:
    """One cell's whole communication round, as a masked ``while_loop``.

    ``mobility`` (static) threads the WorldState carry through the loop:
    each diffusion round deterministically steps the random-waypoint world
    by ``step_m`` meters and recomputes Eqs. 12–14/39 from the stepped
    positions — ``inp.gamma_seq`` then carries the raw Exp(1) Rayleigh
    powers instead of precomputed γ.  ``use_value`` (static) fuses the
    per-client learning value into the Eq.-32 bids via the kernel data
    plane.  Both flags default off, leaving the pre-world trace untouched.
    """
    max_rounds, n, _ = inp.gamma_seq.shape
    m = inp.dol0.shape[0]
    mi = jnp.arange(m)
    pout = outage_probability_jax(inp.gamma_min, inp.mean_snr)   # (N, N)
    state0 = PlannerState(
        dol=jnp.asarray(inp.dol0, jnp.float32),
        chain_size=jnp.asarray(inp.chain_size0, jnp.float32),
        visited=jnp.asarray(inp.visited0, bool),
        holder=jnp.asarray(inp.holder0, jnp.int32),
        world=inp.world if mobility else None)
    bufs0 = PlanOutputs(
        num_rounds=jnp.int32(0),
        dst=jnp.zeros((max_rounds, m), jnp.int32),
        scheduled=jnp.zeros((max_rounds, m), bool),
        src=jnp.zeros((max_rounds, m), jnp.int32),
        gamma=jnp.zeros((max_rounds, m), jnp.float32),
        bandwidth=jnp.zeros((max_rounds, m), jnp.float32),
        decrement=jnp.zeros((max_rounds, m), jnp.float32),
        weight=jnp.zeros((max_rounds, m), jnp.float32),
        efficiency=jnp.zeros((max_rounds,), jnp.float32),
        state=state0,
        final_iid=dol_lib.iid_distance(state0.dol, metric),
        converged=jnp.bool_(True))

    def body(carry):
        st, k, done, out = carry
        if mobility:
            # One deterministic random-waypoint substep per diffusion
            # round, then Eqs. 12–14/39 from the stepped positions — all
            # inside the trace, zero host round-trips.
            from repro.channels.topology import CellTopology
            from repro.channels.world import step as world_step
            w = world_step(st.world, step_m=step_m)
            st = st._replace(world=w)
            dist = CellTopology.pairwise_distances_jax(w.positions)
            p_over_noise, beta0_db, kappa, d0 = (inp.chan[0], inp.chan[1],
                                                 inp.chan[2], inp.chan[3])
            ls_db = beta0_db - 10.0 * kappa * jnp.log10(
                jnp.maximum(dist, d0) / d0)
            mean_snr_k = 10.0 ** (ls_db / 10.0) * p_over_noise   # (N, N)
            pout_k = outage_probability_jax(inp.gamma_min, mean_snr_k)
            h2 = jax.lax.dynamic_index_in_dim(inp.gamma_seq, k, 0,
                                              keepdims=False)
            gamma = spectral_efficiency_jax(mean_snr_k * h2)
        else:
            pout_k = pout
            gamma = jax.lax.dynamic_index_in_dim(inp.gamma_seq, k, 0,
                                                 keepdims=False)
        iid = dol_lib.iid_distance(st.dol, metric)
        active = iid > inp.epsilon
        if not allow_retraining:
            # Models at chain length N visited everyone (full diffusion).
            active &= ~jnp.all(st.visited, axis=1)
        any_active = jnp.any(active)

        # Bids (Eq. 32) and feasibility (18b/c/e + Eq. 39 outage).  The
        # (M, N) candidate scores run through the kernel data plane: the
        # tiled Pallas contraction on TPU / under REPRO_KERNELS_IMPL, the
        # broadcast composite (bit-identical to the host oracle) on the
        # reference path.
        cand = kernel_ops.dol_bid_scores(
            st.dol, st.chain_size, inp.dsi, inp.data_sizes, metric=metric)
        bids = iid[:, None] - cand                           # (M, N)
        if use_value:
            bids = kernel_ops.bid_value_fuse(bids, inp.value,
                                             inp.value_weight)
        gamma_edge = gamma[st.holder]                        # (M, N)
        feas = bids > 0.0
        if not allow_retraining:
            feas &= ~st.visited
        feas &= gamma_edge >= inp.gamma_min
        feas &= pout_k[st.holder] <= inp.outage_max
        feas = feas.at[mi, st.holder].set(False)  # no self-transmission
        bw = required_bandwidth_jax(inp.model_bits, gamma_edge)
        wmat = jnp.where(feas & jnp.isfinite(bw) & (bw > 0.0),
                         bids / bw, 0.0)                     # Eq. 36

        dst0, auc_ok = auction_assign(wmat)                  # Eq. 38 (18d)
        matched = dst0 >= 0
        dstc = jnp.clip(dst0, 0, n - 1)
        w_sel = jnp.where(matched, wmat[mi, dstc], -jnp.inf)
        bw_sel = jnp.where(matched, bw[mi, dstc], 0.0)
        dec_sel = jnp.where(matched, bids[mi, dstc], 0.0)

        # (18f) FCFS over matched edges by decreasing efficiency: an edge
        # that does not fit is skipped, later (cheaper) ones may still fit.
        order = jnp.argsort(-w_sel)

        def fcfs(budget_rem, model):
            cost = bw_sel[model]
            take = matched[model] & (cost <= budget_rem)
            return budget_rem - jnp.where(take, cost, 0.0), take

        _, takes = jax.lax.scan(
            fcfs, jnp.asarray(inp.bandwidth_budget, jnp.float32), order)
        chosen = jnp.zeros((m,), bool).at[order].set(takes) & matched

        n_eff = jnp.sum(chosen & (bw_sel > 0.0))
        eff = jnp.where(
            n_eff > 0,
            jnp.sum(jnp.where(chosen & (bw_sel > 0.0),
                              dec_sel / jnp.maximum(bw_sel, 1e-30), 0.0))
            / jnp.maximum(n_eff, 1), 0.0)

        # Only still-active models actually hop (the matching may pair an
        # inactive model — it competed for PUEs and budget, like the host).
        scheduled = chosen & active
        do = jnp.logical_and(~done, any_active & jnp.any(scheduled))
        sched = scheduled & do
        src = st.holder
        st_new = st.record_round(dstc, sched, inp.dsi, inp.data_sizes)

        def put(buf, row):
            return jax.lax.dynamic_update_index_in_dim(buf, row, k, 0)

        out = out._replace(
            dst=put(out.dst, dstc),
            scheduled=put(out.scheduled, sched),
            src=put(out.src, src),
            gamma=put(out.gamma, gamma[src, dstc]),
            bandwidth=put(out.bandwidth, bw_sel),
            decrement=put(out.decrement, dec_sel),
            weight=put(out.weight, w_sel),
            efficiency=jax.lax.dynamic_update_index_in_dim(
                out.efficiency, eff, k, 0),
            # flag any capped auction on a still-active lane — even one
            # that scheduled nothing may have halted the loop wrongly
            converged=out.converged & (auc_ok | done))
        return st_new, k + do.astype(jnp.int32), done | ~do, out

    def cond(carry):
        _, k, done, _ = carry
        return jnp.logical_and(~done, k < max_rounds)

    state, k, _, out = jax.lax.while_loop(
        cond, body, (state0, jnp.int32(0), jnp.bool_(False), bufs0))
    return out._replace(num_rounds=k, state=state,
                        final_iid=dol_lib.iid_distance(state.dol, metric))


plan_rounds = jax.jit(_plan_rounds,
                      static_argnames=("metric", "allow_retraining",
                                       "mobility", "step_m", "use_value"))


@partial(jax.jit, static_argnames=("metric", "allow_retraining"))
def _plan_rounds_vmapped(stacked: PlanInputs, metric: str,
                         allow_retraining: bool) -> PlanOutputs:
    fn = partial(_plan_rounds, metric=metric,
                 allow_retraining=allow_retraining)
    return jax.vmap(fn)(stacked)


def plan_rounds_batched(inputs: list[PlanInputs], metric: str,
                        allow_retraining: bool) -> list[PlanOutputs]:
    """Plan a batch of cells/rounds in one device call.

    Every item must share shapes (N, M, C, max_rounds) and the static knobs;
    ε / γ_min / outage / budget / model_bits may differ per item (they are
    traced), which is what lets one call cover a whole sweep grid.
    """
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *inputs)
    out = _plan_rounds_vmapped(stacked, metric=metric,
                               allow_retraining=allow_retraining)
    return [jax.tree.map(lambda x, i=i: x[i], out)
            for i in range(len(inputs))]


# ---------------------------------------------------------------- host glue


def draw_gamma_sequence(channel, dist: np.ndarray, rng: np.random.Generator,
                        max_rounds: int,
                        interference: np.ndarray | float = 0.0
                        ) -> np.ndarray:
    """Pre-draw ``max_rounds`` Rayleigh rounds from the host Generator.

    Draw k equals the lazy host loop's draw for diffusion round k (numpy
    Generators are sequential), so host and jax planners see identical
    channels; the jax mode just consumes the stream ``max_rounds`` draws
    deep regardless of where the loop halts.  ``interference`` is the
    per-receiver (or scalar) co-channel power of the multicell world —
    frozen within a communication round, so folding it here keeps the
    planner body interference-free.
    """
    gains = np.stack([channel.sample_gains(dist, rng)
                      for _ in range(max_rounds)])
    return spectral_efficiency(channel.snr(gains, interference))


def draw_fading_sequence(rng: np.random.Generator, n: int,
                         max_rounds: int) -> np.ndarray:
    """(R, N, N) raw Exp(1) Rayleigh powers |h|², stream-identical to the
    draws inside ``channel.sample_gains`` (which consumes exactly one
    ``rng.exponential`` of the distance shape per call).  The mobile world
    consumes these and recomputes β — hence γ — from stepped positions
    inside the planner loop."""
    return np.stack([rng.exponential(scale=1.0, size=(n, n))
                     for _ in range(max_rounds)])


def device_gamma_sequence(channel, key: jax.Array, dist: jax.Array,
                          max_rounds: int) -> jax.Array:
    """Fully device-resident channel draw (no host RNG): ``max_rounds``
    Rayleigh rounds from an explicit PRNG key.  Not parity-preserving with
    the numpy stream — for device-only planning at scale."""
    keys = jax.random.split(key, max_rounds)
    gains = jax.vmap(lambda k: channel.sample_gains_jax(k, dist))(keys)
    return spectral_efficiency_jax(channel.snr_jax(gains))


def plan_round_inputs(planner, state, dsi: np.ndarray,
                      data_sizes: np.ndarray, rng: np.random.Generator,
                      positions: np.ndarray | None = None,
                      interference: np.ndarray | float = 0.0,
                      values: np.ndarray | None = None,
                      value_weight: float = 0.0,
                      world=None) -> tuple[PlanInputs, np.ndarray | None]:
    """Build :class:`PlanInputs` the way the host planner would see them.

    Returns ``(inputs, gamma_seq64)`` — the float64 host-precision channel
    realizations are kept alongside the float32 device copy so
    :func:`decode_plan` can stamp hops with the exact γ the host ledger
    would charge (bit-identical ``bandwidth_hz_s``).

    ``interference`` folds the (frozen-within-round) multicell SINR into
    the pre-drawn γ sequence; ``values``/``value_weight`` populate the
    learning-value fields; ``world`` (a float32 WorldState) switches to
    mobile form — ``gamma_seq`` then carries raw Exp(1) powers, the
    channel constants ride in ``chan``, and ``gamma_seq64`` is ``None``
    (γ is computed in-loop at float32).
    """
    n = dsi.shape[0]
    chan = planner.channel
    if world is not None:
        positions = np.asarray(world.positions)
    elif positions is None:
        positions = planner.topology.sample_positions(rng, n)
    dist = planner.topology.pairwise_distances(positions)
    beta = 10 ** (chan.large_scale_db(dist) / 10.0)
    mean_snr = chan.snr(beta, interference)
    max_rounds = planner.max_rounds or n * (n - 1)
    if world is not None:
        seq = draw_fading_sequence(rng, n, max_rounds)
        gamma_seq64 = None
        p = chan.params
        chan_vec = jnp.asarray([p.tx_power_w / p.noise_w, p.beta0_db,
                                p.kappa, p.d0_m], jnp.float32)
    else:
        seq = draw_gamma_sequence(chan, dist, rng, max_rounds, interference)
        gamma_seq64 = seq
        chan_vec = None
    a = planner.auction
    use_value = values is not None and value_weight != 0.0
    return PlanInputs(
        dol0=jnp.asarray(state.dol, jnp.float32),
        chain_size0=jnp.asarray(state.chain_size, jnp.float32),
        visited0=jnp.asarray(state.visited, bool),
        holder0=jnp.asarray(state.holder, jnp.int32),
        dsi=jnp.asarray(dsi, jnp.float32),
        data_sizes=jnp.asarray(data_sizes, jnp.float32),
        gamma_seq=jnp.asarray(seq, jnp.float32),
        mean_snr=jnp.asarray(mean_snr, jnp.float32),
        epsilon=jnp.float32(planner.epsilon),
        gamma_min=jnp.float32(a.gamma_min),
        outage_max=jnp.float32(a.outage_max),
        bandwidth_budget=jnp.float32(a.bandwidth_budget),
        model_bits=jnp.float32(a.model_bits),
        value=(jnp.asarray(values, jnp.float32) if use_value else None),
        value_weight=(jnp.float32(value_weight) if use_value else None),
        world=(jax.tree.map(jnp.asarray, world) if world is not None
               else None),
        chan=chan_vec), gamma_seq64


def decode_plan(out: PlanOutputs, num_models: int,
                gamma_seq64: np.ndarray | None = None,
                model_bits: float | None = None):
    """Padded plan tensors → host :class:`~repro.core.diffusion.DiffusionPlan`.

    Hops within a round are emitted in decreasing Eq.-36 weight — the host
    planner's FCFS order — so the two modes produce identical hop lists.
    When the float64 channel realizations (and S) are provided, hop γ and
    Eq.-15 bandwidth are re-read at host precision, making ledger charges
    bit-identical to the host planner's.
    """
    from repro.core.diffusion import DiffusionHop, DiffusionPlan
    k = int(out.num_rounds)
    sched = np.asarray(out.scheduled)
    dst = np.asarray(out.dst)
    src = np.asarray(out.src)
    gamma = np.asarray(out.gamma)
    bw = np.asarray(out.bandwidth)
    dec = np.asarray(out.decrement)
    weight = np.asarray(out.weight)
    eff = np.asarray(out.efficiency)
    hops = []
    for r in range(k):
        models = [int(m) for m in np.flatnonzero(sched[r])]
        models.sort(key=lambda m: -weight[r, m])
        for m in models:
            s, d = int(src[r, m]), int(dst[r, m])
            if gamma_seq64 is not None:
                g = float(gamma_seq64[r, s, d])
                b = (float(model_bits) / g if model_bits is not None
                     else float(bw[r, m]))
            else:
                g, b = float(gamma[r, m]), float(bw[r, m])
            hops.append(DiffusionHop(
                model=m, src=s, dst=d, gamma=g, bandwidth=b,
                decrement=float(dec[r, m]), round_index=r))
    return DiffusionPlan(
        hops=hops, num_rounds=k,
        final_iid_distance=np.asarray(out.final_iid),
        efficiency_per_round=[float(e) for e in eff[:k]],
        num_models=num_models)


def plan_communication_round_jax(planner, state, dsi: np.ndarray,
                                 data_sizes: np.ndarray,
                                 rng: np.random.Generator,
                                 positions: np.ndarray | None = None,
                                 cache=None, cache_key: tuple | None = None,
                                 interference: np.ndarray | float = 0.0,
                                 values: np.ndarray | None = None,
                                 value_weight: float = 0.0,
                                 world=None, step_m: float = 0.0):
    """Jax-mode twin of ``DiffusionPlanner.plan_communication_round``:
    same signature/contract (mutates ``state``, consults the cache), but the
    whole bid → auction → schedule loop runs in one jitted device call."""
    if planner.underlay:
        raise ValueError("the jax planner does not model underlay CUE "
                         "interference; use planner='host' for underlay "
                         "scenarios (Appendix C-F)")
    if cache is not None and cache_key is not None:
        entry = cache.lookup(cache_key)
        if entry is not None:
            plan, post_state = entry
            state.restore(post_state)
            return plan
    with obs.span("fl.plan.inputs"):
        inp, gamma64 = plan_round_inputs(
            planner, state, dsi, data_sizes, rng, positions,
            interference=interference, values=values,
            value_weight=value_weight, world=world)
    with obs.span("fl.plan.auction"):
        out = plan_rounds(inp, metric=planner.auction.metric,
                          allow_retraining=planner.auction.allow_retraining,
                          mobility=world is not None, step_m=float(step_m),
                          use_value=inp.value is not None)
        converged = bool(out.converged)      # waits for the device plan
    if not converged:
        warnings.warn("jax planner: an auction hit its iteration cap; the "
                      "plan may schedule fewer hops than the host oracle",
                      RuntimeWarning, stacklevel=2)
    with obs.span("fl.plan.decode"):
        plan = decode_plan(out, num_models=state.dol.shape[0],
                           gamma_seq64=gamma64,
                           model_bits=planner.auction.model_bits)
        state.update_from(out.state, rounds_advanced=int(out.num_rounds))
    if cache is not None and cache_key is not None:
        cache.store(cache_key, plan, state)
    return plan
