"""Executors: run one :class:`~repro.core.schedule.RoundSchedule` on params.

Two data planes consume the same schedule object:

* :class:`HostExecutor` — the reference semantics.  One parameter pytree per
  client slot, local updates through ``repro.fl.client`` /
  ``repro.fl.fedprox`` exactly as the original per-strategy loops did
  (same per-client batch draws, same jitted step, same aggregation order),
  so refactored strategies reproduce their pre-schedule trajectories.

* :class:`FleetExecutor` — the client-stacked fast path.  All slots live on
  one pytree with a leading client axis; a local "session" (one epoch of
  batches, momentum restarted, per-slot gradient clipping) is a jitted
  ``vmap`` over that axis, a diffusion hop is
  :func:`~repro.distributed.fedshard.diffuse_params`, STC hops use
  :func:`~repro.distributed.fedshard.masked_stc_compress`, and Eq.-11
  aggregation is one weighted ``tensordot``.  Clients with shorter epochs
  are padded and masked out per step, so the math per client matches the
  host loop; the win is dispatch count — O(max-epoch) jitted calls per op
  instead of O(Σ client batches) — which is what lets sweeps scale past
  paper-sized fleets.  A session that trains few slots runs on a compact
  stack of them, at the smallest quarter of the fleet that holds them
  (:func:`compact_slots`); every such width compiles in the first session.

* :class:`ShardedFleetExecutor` — the large-N plane, on the 2-D
  ``("clients", "model")`` mesh of :func:`repro.launch.mesh.make_fl_mesh`.
  The stacked pytree's leading client axis is *sharded* over the combined
  mesh axis (:func:`repro.distributed.sharding.fl_stacked_specs`), padded
  with zero-weighted slots when N does not divide the mesh, and runs in one
  of two shard_map planes selected by ``EngineSpec.shard_overlap``:

  - the **op-by-op plane** (``shard_overlap="off"``): one compiled
    collective per schedule op — sessions are ``shard_map``-ped with the
    per-shard block microbatched (``lax.map`` over chunks of
    ``EngineSpec.shard_microbatch`` clients) so N=256–4096
    fleets fit in memory, a :class:`~repro.core.schedule.PermuteOp` is a
    ring-shift-decomposed permutation collective (static routing tables +
    per-shift ``lax.ppermute``; with a model axis the flattened parameter
    block is first feature-split over ``"model"`` via ``all_to_all`` so
    each shift moves only F/km bytes per link), a
    :class:`~repro.core.schedule.MixOp` is Wᵀ-partials + ``psum_scatter``,
    and Eq.-11 aggregation is a masked ``psum`` over the combined axis.

  - the **fused round plane** (``"on"``; ``"auto"`` resolves to it): the
    whole round — broadcast, sessions, STC hops, permutes, mixes,
    aggregation — is ONE jitted shard_map program per round signature.
    Hop k's ring shifts are issued per *double-buffered chunk*: the send
    buffers of chunk j+1 depend only on pre-hop state, so their collectives
    can overlap chunk j's training compute (async collectives where the
    backend supports them; on CPU the win is dispatch count — a handful of
    device calls per round instead of O(hops × steps)).

  On a 1-device mesh both planes degenerate to the fleet program.

Under ``FLConfig.hop_quant == "int8"`` every PermuteOp payload crosses the
wire int8-packed (``repro.fl.adapters``): each executor applies exactly one
pack→unpack roundtrip per hop to every slot — the host roundtrips slot
trees, the fleet roundtrips the stacked pytree, and the sharded planes move
the packed codes + scales through the very ring/gather collectives that
implement the hop.  Per-row packing commutes with row movement, so the
three placements stay numerically identical.

Ledger charging lives in none of them: :func:`~repro.core.schedule
.charge_schedule` replays the schedule's wire events, so all executors
report identical communication metrics by construction.
"""
from __future__ import annotations

import copy
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import aggregation as agg
from repro.core.schedule import MixOp, PermuteOp, RoundSchedule, TrainOp
from repro.distributed.fedshard import diffuse_params, masked_stc_compress
from repro.distributed.sharding import CLIENT_AXIS, FL_AXES, MODEL_AXIS
from repro.fl.adapters import (FrozenLoss, pack_rows, quant_roundtrip_rows,
                               quant_roundtrip_slot, quant_roundtrip_tree,
                               unpack_rows)
from repro.fl.compression import stc_compress
from repro.fl.schedulers import PROX_STRATEGIES
from repro.kernels import ops as kernel_ops
from repro.kernels.diffusion import stack_ravel, stack_unravel
from repro.train import optimizer as opt_lib

Params = Any

__all__ = ["HostExecutor", "FleetExecutor", "ShardedFleetExecutor",
           "make_executor", "EXECUTORS"]

EXECUTORS = ("host", "fleet", "sharded")


def session_widths(num_slots: int) -> tuple[int, ...]:
    """The widths a fleet session over ``num_slots`` slots runs at: the
    quarters of ``num_slots``, rounded up (4, 8, 12, 16 for 16 slots)."""
    return tuple(sorted({-(-j * num_slots // 4) for j in range(1, 5)}))


def compact_slots(mask: np.ndarray) -> np.ndarray:
    """The slots a session of ``mask`` computes: its training slots in slot
    order, then the first idle slots, up to the smallest of
    :func:`session_widths` that holds the training ones.  The idle slots
    are distinct and masked out, so they return their rows unchanged."""
    mask = np.asarray(mask, dtype=bool)
    on = np.flatnonzero(mask)
    width = next(b for b in session_widths(len(mask)) if b >= len(on))
    return np.concatenate([on, np.flatnonzero(~mask)[:width - len(on)]]
                          ).astype(np.int32)


def _replayed(fn: Callable) -> Callable:
    """``fn`` traced once per signature of its arguments, then replayed
    from that jaxpr.  A caller that traces it again, as the fleet step's
    ``vmap`` does at each session width, batches the jaxpr instead of
    running ``fn``'s Python (a large model's loss and its gradient) anew."""
    jaxprs: dict = {}

    @functools.wraps(fn)
    def replay(*args):
        leaves, tree = jax.tree.flatten(args)
        avals = tuple(jax.typeof(x) for x in leaves)
        if (tree, avals) not in jaxprs:
            abstract = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             weak_type=a.weak_type)
                        for a in avals]
            jaxprs[tree, avals] = jax.make_jaxpr(fn, return_shape=True)(
                *jax.tree.unflatten(tree, abstract))
        closed, shape = jaxprs[tree, avals]
        return jax.tree.unflatten(
            jax.tree.structure(shape),
            jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *leaves))
    return replay


def _tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


def _tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


class HostExecutor:
    """Per-slot pytree-list execution — the bit-for-bit reference path."""

    def __init__(self, local_update: Callable,
                 client_batches: Sequence[Callable], cfg):
        self.local_update = local_update
        self.client_batches = client_batches
        self.cfg = cfg
        self.quant = str(getattr(cfg, "hop_quant", "none")) == "int8"

    def _train(self, slots: list, mask: np.ndarray) -> None:
        for c in np.flatnonzero(mask):
            slots[c], _ = self.local_update(
                slots[c], self.client_batches[c](), self.cfg.lr)

    # ------------------------------------------------- round-state capture
    # Persistent strategies (gossip, tthf) carry per-slot state across
    # communication rounds; the resume seam (repro.fl.resume) round-trips it
    # through these three hooks so a checkpoint taken under any executor
    # restores onto the same executor bit-identically.

    def capture_slots(self, slots: list | None):
        """Host-resident copy of the persistent slot state (or ``None``)."""
        return None if slots is None else jax.device_get(slots)

    def slots_like(self, global_params: Params, num_slots: int):
        """Shape/dtype template matching :meth:`capture_slots` output."""
        leaf = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        return [jax.tree.map(leaf, global_params) for _ in range(num_slots)]

    def num_slots_of(self, saved) -> int:
        """Slot count of a :meth:`capture_slots` capture (host: outer list).

        The executor is authoritative here — the capture's pytree structure
        alone is ambiguous (a model whose params are themselves a list looks
        like a host slot-list)."""
        return len(saved)

    def adopt_slots(self, saved):
        """Executor-native placement of a captured slot tree."""
        return saved

    def run_ops(self, sched: RoundSchedule, global_params: Params,
                slots: list | None) -> list:
        """Replay the schedule's op list; return the post-op slot state.

        The first half of :meth:`run_round` — the buffered-async plane runs
        it per round, then defers :meth:`aggregate` to arrival order."""
        c_slots = sched.num_slots
        if not sched.persistent or slots is None:
            slots = [copy.deepcopy(global_params) for _ in range(c_slots)]
        ref = global_params
        for op in sched.ops:
            if isinstance(op, TrainOp):
                self._train(slots, op.train_mask)
            elif isinstance(op, PermuteOp):
                if op.compress:
                    for s in np.flatnonzero(op.compress_src_mask()):
                        delta = stc_compress(_tree_sub(slots[s], ref),
                                             sched.stc_sparsity)
                        slots[s] = _tree_add(ref, delta)
                if self.quant:
                    # int8 wire: what each destination decodes is the
                    # pack→unpack of the payload (hop is a bijection, so
                    # every slot moves and is roundtripped exactly once).
                    slots = [quant_roundtrip_slot(s) for s in slots]
                slots = [slots[int(op.src_of_dst[c])] for c in range(c_slots)]
                self._train(slots, op.train_mask)
            elif isinstance(op, MixOp):
                for members, weights in op.groups:
                    avg = agg.fedavg([slots[i] for i in members],
                                     list(weights))
                    for i in members:
                        slots[i] = avg
            else:
                raise TypeError(f"unknown op {type(op).__name__}")
        return slots

    def slot_state(self, slots: list, slot: int) -> Params:
        """The post-op payload of one slot (host: its pytree)."""
        return slots[slot]

    def aggregate(self, sched: RoundSchedule, slots: list,
                  ref: Params) -> Params:
        """Eq. (11) over the schedule's ``agg`` entries, in entry order."""
        weights = [w for _, w in sched.agg]
        if sched.agg_mode == "stc_delta":
            deltas = [stc_compress(_tree_sub(slots[s], ref),
                                   sched.stc_sparsity) for s, _ in sched.agg]
            return _tree_add(ref, agg.fedavg(deltas, weights))
        return agg.fedavg([slots[s] for s, _ in sched.agg], weights)

    def run_round(self, sched: RoundSchedule, global_params: Params,
                  slots: list | None) -> tuple[Params, list | None]:
        slots = self.run_ops(sched, global_params, slots)
        new_global = self.aggregate(sched, slots, global_params)
        return new_global, (slots if sched.persistent else None)


class FleetExecutor:
    """Client-stacked execution: one pytree, leading client axis, jitted.

    A :class:`~repro.fl.adapters.FrozenLoss` hands its frozen tree to the
    jitted step as a last, unbatched argument (``in_axes=None``): every
    slot reads the one copy on the device, and no compiled program holds
    it as a constant.  Its step also returns the loss's counts, per slot,
    which a recorded session hands to ``repro.obs`` unread."""

    def __init__(self, loss_fn: Callable,
                 client_batches: Sequence[Callable], cfg,
                 clip: float | None = 10.0):
        self.loss_fn = loss_fn
        self.client_batches = client_batches
        self.cfg = cfg
        self.quant = str(getattr(cfg, "hop_quant", "none")) == "int8"
        self.prox = cfg.strategy in PROX_STRATEGIES
        opt = opt_lib.sgd(momentum=cfg.momentum)
        mu = float(cfg.prox_mu)
        if isinstance(loss_fn, FrozenLoss):
            self._frozen = (loss_fn.frozen,)
            loss_of = loss_fn.fn
        else:
            self._frozen = ()
            loss_of = loss_fn
        self._frozen_bytes = sum(x.size * x.dtype.itemsize
                                 for x in jax.tree.leaves(self._frozen))

        def one(p, mom, batch, active, anchor, *frozen):
            def obj(q):
                loss, counts = (loss_of(q, batch, *frozen) if frozen
                                else (loss_of(q, batch), ()))
                if self.prox:
                    prox = sum(jnp.sum((a.astype(jnp.float32)
                                        - b.astype(jnp.float32)) ** 2)
                               for a, b in zip(jax.tree.leaves(q),
                                               jax.tree.leaves(anchor)))
                    loss = loss + 0.5 * mu * prox
                return loss, counts

            (loss, counts), grads = jax.value_and_grad(obj, has_aux=True)(p)
            if clip is not None:
                grads, _ = opt_lib.clip_by_global_norm(grads, clip)
            updates, new_state = opt.update(grads, {"mu": mom}, p, cfg.lr)
            p2 = opt_lib.apply_updates(p, updates)
            sel = functools.partial(jnp.where, active)
            return (jax.tree.map(sel, p2, p),
                    jax.tree.map(sel, new_state["mu"], mom), loss) \
                + ((counts,) if frozen else ())

        self._one = one          # per-client step; ShardedFleetExecutor remaps
        # Params and momentum are replaced every step, so the step donates
        # them: the outputs reuse the inputs' device buffers, which is what
        # lets a client-stacked full-width model fit on one chip.  Every
        # width of it replays one trace of ``one``.
        self._vstep = jax.jit(
            jax.vmap(_replayed(one),
                     in_axes=(0,) * 5 + (None,) * len(self._frozen)),
            donate_argnums=(0, 1))
        self._step = self._vstep

        def take(stack, idx):
            # A compact session's rows, its zero momentum and, under prox,
            # its anchor: a buffer of its own, as the step donates the rows.
            rows = jax.tree.map(lambda x: x[idx], stack)
            mom = jax.tree.map(lambda r: jnp.zeros_like(r, jnp.float32),
                               rows)
            return rows, mom, (rows if self.prox else None)

        def put(stack, idx, rows):
            return jax.tree.map(
                lambda s, r: s.at[idx].set(r, unique_indices=True),
                stack, rows)

        self._take = jax.jit(take)
        self._put = jax.jit(put, donate_argnums=0)
        # (fleet size, batch signature) -> {width: (take, step, put)}
        self._compact: dict = {}

    # ------------------------------------------------------------------ spans

    @staticmethod
    def _traced(name: str, fn, *args):
        """Run a round primitive inside the ``repro.obs`` span ``name``
        (``fl.exec.*``); a no-op wrapper while recording is off."""
        with obs.span(name):
            return fn(*args)

    # ---------------------------------------------------------------- batches

    def _draw_session(self, mask: np.ndarray,
                      slots: np.ndarray | None = None):
        """Draw one local epoch per *masked* slot (preserving each client's
        host-side batch stream), pad to the longest epoch, stack per step
        the rows of ``slots`` (every slot by default).

        Returns ``(steps, actives)``: per padded step, a slot-stacked batch
        dict and the bool mask of the stacked slots genuinely training that
        step.
        """
        with obs.span("fl.exec.draw"):
            per_slot = [list(self.client_batches[c]()) if mask[c] else []
                        for c in range(len(mask))]
            nb = max((len(b) for b in per_slot), default=0)
            if nb == 0:
                return [], []
            template = jax.tree.map(
                np.zeros_like, next(b[0] for b in per_slot if b))
            stacked = (per_slot if slots is None
                       else [per_slot[c] for c in slots])
            steps, actives = [], []
            for k in range(nb):
                rows = [b[k] if k < len(b) else template for b in stacked]
                steps.append(jax.tree.map(
                    lambda *xs: jnp.asarray(np.stack(xs)), *rows))
                actives.append(jnp.asarray(
                    np.array([k < len(b) for b in stacked])))
            if obs.enabled():
                obs.count("fl.exec.slot_steps", len(mask) * nb)
                obs.count("fl.exec.computed_slot_steps", len(stacked) * nb)
                obs.count("fl.exec.active_slot_steps",
                          sum(len(b) for b in per_slot))
                obs.count("fl.exec.h2d_bytes", sum(
                    x.nbytes for x in jax.tree.leaves((steps, actives))))
        return steps, actives

    def _session(self, params: Params, mask: np.ndarray) -> Params:
        """One local-update session at every masked slot (vmapped epoch),
        computed on a compact stack of :func:`compact_slots` where that is
        narrower than the fleet: gathered once, stepped, scattered back."""
        if not mask.any():
            return params
        idx = compact_slots(mask)
        full = len(idx) == len(mask)
        steps, actives = self._draw_session(mask, None if full else idx)
        if not steps:
            return params
        programs = self._compact_programs(params, steps[0])
        obs.count("fl.exec.compacted_sessions", int(not full))
        if full:
            return self._full_session(params, steps, actives)
        take, step, put = programs[len(idx)]
        index = jnp.asarray(idx)
        rows, mom, anchor = take(params, index)
        rows = self._run_steps(step, rows, mom, anchor, steps, actives)
        return put(params, index, rows)

    def _full_session(self, params: Params, steps: list,
                      actives: list) -> Params:
        """A drawn session over every slot of ``params``, through
        ``_step``."""
        mom = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        # prox anchor = the received model (host default); a copy of its
        # own, because the fleet step donates the params buffers it reads.
        anchor = jax.tree.map(jnp.copy, params) if self.prox else None
        return self._run_steps(self._step, params, mom, anchor, steps,
                               actives)

    def _run_steps(self, step, params: Params, mom: Params, anchor,
                   steps: list, actives: list) -> Params:
        obs.count("fl.exec.steps", len(steps))
        if self._frozen_bytes and obs.enabled():
            # Once an executor: the frozen tree every step reads.
            obs.count("fl.exec.frozen_bytes", self._frozen_bytes)
            self._frozen_bytes = 0
        for batch, active in zip(steps, actives):
            params, mom, _, *counts = step(params, mom, batch, active,
                                           anchor, *self._frozen)
            if counts and obs.enabled():
                for name, n in counts[0].items():
                    obs.count(name, n)
        return params

    def _compact_programs(self, params: Params, batch) -> dict:
        """The take, step and put of every compact width of this fleet, for
        this batch signature, compiled the first time the signature is met
        (the first session): a width first used mid-run compiles nothing.
        Compiled from the step's jit itself, never through ``_step``, so a
        caller that wraps ``_step`` sees full-width sessions alone."""
        c = jax.tree.leaves(params)[0].shape[0]
        leaves, tree = jax.tree.flatten(batch)
        key = (c, tree, tuple((x.shape[1:], x.dtype) for x in leaves))
        if key not in self._compact:
            def rows(x, b):
                return jax.ShapeDtypeStruct((b,) + x.shape[1:], x.dtype)
            programs = {}
            for b in session_widths(c)[:-1]:
                idx = jax.ShapeDtypeStruct((b,), jnp.int32)
                take = self._take.lower(params, idx).compile()
                p, mom, anchor = jax.eval_shape(self._take, params, idx)
                step = self._vstep.lower(
                    p, mom, jax.tree.map(lambda x: rows(x, b), batch),
                    jax.ShapeDtypeStruct((b,), jnp.bool_), anchor,
                    *self._frozen).compile()
                put = self._put.lower(params, idx, p).compile()
                programs[b] = (take, step, put)
            self._compact[key] = programs
        return self._compact[key]

    # ------------------------------------------------- round-state capture

    def capture_slots(self, slots: Params | None):
        return None if slots is None else jax.device_get(slots)

    def slots_like(self, global_params: Params, num_slots: int):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((num_slots,) + x.shape, x.dtype),
            global_params)

    def num_slots_of(self, saved) -> int:
        """Slot count of a capture (fleet: the stacked leading axis)."""
        return int(jax.tree.leaves(saved)[0].shape[0])

    def adopt_slots(self, saved):
        return jax.tree.map(jnp.asarray, saved)

    # ----------------------------------------------- overridable primitives
    # One round structure (run_round below), two placements:
    # ShardedFleetExecutor overrides exactly these five hooks with its
    # collective twins, so a new op kind or agg mode is added in one place.

    def _broadcast(self, global_params: Params, num_slots: int) -> Params:
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_slots,) + x.shape),
            global_params)

    def _permute(self, params: Params, op: PermuteOp) -> Params:
        if self.quant:
            # int8 wire: roundtrip the stacked payload per client row, then
            # move the decoded rows (packing commutes with row gathers).
            params = quant_roundtrip_tree(params)
        return diffuse_params(params, jnp.asarray(op.src_of_dst))

    def _mix(self, params: Params, op: MixOp, num_slots: int) -> Params:
        # Eq. (10) through the kernel data plane: the fused single-HBM-pass
        # Pallas kernel on TPU / under REPRO_KERNELS_IMPL, the per-leaf
        # einsum chain on the XLA reference path.
        w = jnp.asarray(op.matrix(num_slots), jnp.float32)
        return kernel_ops.mix_aggregate_tree(params, w)

    def _masked_stc(self, params: Params, ref: Params, mask: np.ndarray,
                    sparsity: float) -> Params:
        return masked_stc_compress(params, ref, jnp.asarray(mask), sparsity)

    def _aggregate(self, payload: Params, w: jax.Array) -> Params:
        # Eq. (11): aggregation is the same kernel with one output row.
        return kernel_ops.mix_aggregate_tree(
            payload, w.astype(jnp.float32).reshape(1, -1), collapse=True)

    # ------------------------------------------------------------------ round

    def run_ops(self, sched: RoundSchedule, global_params: Params,
                slots: Params | None) -> Params:
        """Replay the op list on the client-stacked pytree (first half of
        :meth:`run_round` — see :meth:`HostExecutor.run_ops`)."""
        c_slots = sched.num_slots
        if sched.persistent and slots is not None:
            params = slots
        else:
            params = self._traced("fl.exec.broadcast", self._broadcast,
                                  global_params, c_slots)
        ref = global_params
        for op in sched.ops:
            if isinstance(op, TrainOp):
                params = self._traced("fl.exec.train", self._session, params,
                                      op.train_mask)
            elif isinstance(op, PermuteOp):
                if op.compress:
                    params = self._traced("fl.exec.hop", self._masked_stc,
                                          params, ref,
                                          op.compress_src_mask(),
                                          sched.stc_sparsity)
                params = self._traced("fl.exec.hop", self._permute, params,
                                      op)
                params = self._traced("fl.exec.train", self._session, params,
                                      op.train_mask)
            elif isinstance(op, MixOp):
                params = self._traced("fl.exec.mix", self._mix, params, op,
                                      c_slots)
            else:
                raise TypeError(f"unknown op {type(op).__name__}")
        return params

    def slot_state(self, params: Params, slot: int) -> Params:
        """The post-op payload of one slot (fleet: its stacked-axis row)."""
        return jax.tree.map(lambda x: x[slot], params)

    def aggregate(self, sched: RoundSchedule, params: Params,
                  ref: Params) -> Params:
        wvec = sched.slot_weights()
        w = jnp.asarray((wvec / wvec.sum()).astype(np.float32))
        if sched.agg_mode == "stc_delta":
            payload = self._traced("fl.exec.aggregate", self._masked_stc,
                                   params, ref, wvec > 0, sched.stc_sparsity)
        else:
            payload = params
        return self._traced("fl.exec.aggregate", self._aggregate, payload, w)

    def run_round(self, sched: RoundSchedule, global_params: Params,
                  slots: Params | None) -> tuple[Params, Params | None]:
        params = self.run_ops(sched, global_params, slots)
        new_global = self.aggregate(sched, params, global_params)
        return new_global, (params if sched.persistent else None)


def _permutation_tables(src_of_dst: np.ndarray, num_shards: int
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Static routing tables for a slot bijection on a ``num_shards`` mesh.

    The global permutation ``new[c] = old[src_of_dst[c]]`` is decomposed into
    ``num_shards`` ring shifts: rows moving from shard ``s`` to shard
    ``(s + shift) % K`` travel together in one ``ppermute`` step.  Returns

    * ``send[s, shift, i]`` — local row index the *source* shard ``s`` packs
      at buffer position ``i`` for shift ``shift`` (0-padded), and
    * ``recv[d, shift, i]`` — local row index where the *destination* shard
      ``d`` scatters buffer position ``i`` (padded with ``n_local``, a trash
      row dropped after the scatter).

    Packing order ``i`` is shared between the two tables because a
    ``(shift, src)`` pair determines the destination shard uniquely.  The
    tables are data, not code: one compiled collective serves every
    permutation of a round without retracing.
    """
    perm = np.asarray(src_of_dst, np.int64)
    c = perm.shape[0]
    k = num_shards
    assert c % k == 0, (c, k)
    nl = c // k
    send = np.zeros((k, k, nl), np.int32)
    recv = np.full((k, k, nl), nl, np.int32)
    fill = np.zeros((k, k), np.int32)
    for dst in range(c):
        src = int(perm[dst])
        s, d = src // nl, dst // nl
        shift = (d - s) % k
        i = int(fill[shift, s])
        fill[shift, s] = i + 1
        send[s, shift, i] = src % nl
        recv[d, shift, i] = dst % nl
    return send, recv


def _chunked_permutation_tables(src_of_dst: np.ndarray, num_shards: int,
                                num_chunks: int
                                ) -> tuple[np.ndarray, np.ndarray]:
    """:func:`_permutation_tables` split by *destination chunk* — the
    double-buffered stage tables of the fused round plane.

    The local rows of every destination shard are cut into ``num_chunks``
    contiguous chunks of ``mb = n_local / num_chunks`` rows; the rows
    landing in chunk ``j`` travel in their own per-shift buffers, so chunk
    ``j+1``'s collectives depend only on the *pre-hop* state and can be
    issued while chunk ``j`` trains.  Returns

    * ``send[s, j, shift, i]`` — local row the source shard ``s`` packs at
      position ``i`` of the (chunk ``j``, ``shift``) buffer (0-padded), and
    * ``recv[d, j, shift, i]`` — *chunk-relative* row where destination
      ``d`` scatters position ``i`` (padded with ``mb``, a trash row).

    A ``(shift, src, chunk)`` triple determines the destination shard, so
    the packing order is shared exactly as in the unchunked tables; a
    buffer never overflows ``mb`` because chunk ``j`` only has ``mb`` rows.
    """
    perm = np.asarray(src_of_dst, np.int64)
    c = perm.shape[0]
    k = num_shards
    assert c % k == 0, (c, k)
    nl = c // k
    assert nl % num_chunks == 0, (nl, num_chunks)
    mb = nl // num_chunks
    send = np.zeros((k, num_chunks, k, mb), np.int32)
    recv = np.full((k, num_chunks, k, mb), mb, np.int32)
    fill = np.zeros((k, num_chunks, k), np.int32)
    for dst in range(c):
        src = int(perm[dst])
        s, d = src // nl, dst // nl
        r = dst % nl
        j = r // mb
        shift = (d - s) % k
        i = int(fill[s, j, shift])
        fill[s, j, shift] = i + 1
        send[s, j, shift, i] = src % nl
        recv[d, j, shift, i] = r - j * mb
    return send, recv


class ShardedFleetExecutor(FleetExecutor):
    """Client-sharded execution over the 2-D ``("clients", "model")`` mesh.

    Same math as :class:`FleetExecutor` (it reuses the per-client step and
    the host-side batch streams verbatim); the difference is placement and
    program shape:

    * **Layout.**  The leading client axis of every leaf is sharded over the
      *combined* mesh axes; N is padded to ``c_pad`` (next multiple of the
      mesh size) with zero-weighted padding slots — identity rows in mix
      matrices, identity extensions of hop permutations, ``False`` training
      masks — so padding never leaks into real slots and no divisibility is
      required of N.  During a hop with ``km > 1`` the flattened parameter
      block is feature-split over ``"model"`` (``all_to_all``), each
      ``"clients"``-ring ``ppermute`` then moves F/km bytes per link, and
      the inverse ``all_to_all`` restores the train layout.

    * **Planes.**  ``EngineSpec.shard_overlap`` picks between the inherited
      op-by-op round loop (one compiled collective per schedule op) and
      the fused round plane: the
      whole round is ONE jitted shard_map program per round *signature*
      (op kinds + step counts + compress/agg flags + hop transport), with
      each hop's ring shifts issued per double-buffered destination chunk
      so chunk j+1's collectives — which depend only on pre-hop state —
      overlap chunk j's training compute.

    * **Hop transport.**  ``EngineSpec.shard_hop_transport`` picks the fused
      plane's hop collective: ``"gather"`` (one tiled ``all_gather`` over
      the combined axes + local row-take — a single rendezvous per hop)
      or ``"ring"`` (kc ``ppermute`` shifts, O(block) memory, double
      buffered).  ``"auto"`` takes gather while the gathered ``(c_pad, F)``
      stack fits ``GATHER_BUDGET_BYTES`` per device and rings past it.

    * **Signature stability.**  Every distinct round signature is a fresh
      trace + XLA compile of the whole-round program — at N ≥ 1024 that
      retrace dominated the round wall-clock, because both the diffusion
      wave count and the ragged epoch lengths vary per round.  The fused
      plane therefore normalizes the signature: all session step counts
      pad to a running maximum (padded steps carry all-``False`` active
      masks and are skipped at runtime by a ``lax.cond`` that sits outside
      the vmap), and each run of hop segments pads to a multiple of
      ``FUSED_WAVE_BUCKET`` with identity no-op waves (identity routing,
      nothing trains, zero wire charge).  Padding is executor-internal —
      exactly like the ``c_pad`` slot padding, it never touches real
      slots, so ledger and parameter parity are preserved bit-identically.
    """

    def __init__(self, loss_fn: Callable,
                 client_batches: Sequence[Callable], cfg,
                 clip: float | None = 10.0, mesh=None, *,
                 mesh_model_axis: int = 1, shard_microbatch: int = 32,
                 shard_overlap: str = "auto",
                 shard_hop_transport: str = "auto"):
        super().__init__(loss_fn, client_batches, cfg, clip)
        from repro.launch.mesh import make_fl_mesh
        c = cfg.num_clients
        if mesh is None:
            mesh = make_fl_mesh(c, model=mesh_model_axis)
        self.mesh = mesh
        shape = dict(mesh.shape)
        self.kc = int(shape[CLIENT_AXIS])
        self.km = int(shape.get(MODEL_AXIS, 1))
        # A caller-supplied 1-D ("clients",) mesh still works: the model
        # axis degenerates and every spec collapses to P(("clients",)).
        self._axes = FL_AXES if MODEL_AXIS in shape else (CLIENT_AXIS,)
        self.k = self.kc * self.km
        self.c = c
        self.c_pad = -(-c // self.k) * self.k
        self.nl = self.c_pad // self.k        # train-layout rows per device
        self.nl_hop = self.c_pad // self.kc   # hop-layout rows per ring slot
        mb_cap = max(1, shard_microbatch)
        self.mb = max(b for b in range(1, min(mb_cap, self.nl) + 1)
                      if self.nl % b == 0)
        self.nchunks = self.nl // self.mb
        # Fused-plane double buffering: two destination chunks per hop when
        # the local block splits evenly.  Chunk j+1's send gathers read only
        # pre-hop state, so its collectives can issue while chunk j trains.
        self.fused_chunks = 2 if (self.km == 1 and self.nl % 2 == 0) else 1
        self.fused_mb = self.nl // self.fused_chunks
        assert shard_overlap in ("auto", "on", "off"), shard_overlap
        # Below FUSED_MIN_CLIENTS the fused program's compile cost and
        # round-signature sensitivity outweigh the dispatch it saves —
        # "auto" therefore takes the fused plane only for large fleets.
        self.overlap = shard_overlap == "on" or (
            shard_overlap == "auto" and c >= self.FUSED_MIN_CLIENTS)
        assert shard_hop_transport in ("auto", "ring", "gather"), \
            shard_hop_transport
        self._transport_req = shard_hop_transport
        self._transport: str | None = None     # resolved on first fused round
        self._stc_cache: dict = {}
        self._fused_cache: dict = {}
        # Fused-plane signature normalization (see class docstring): the
        # running per-segment step maximum, and a zero batch template for
        # the cond-skipped padding steps (set on the first drawn step).
        self._nb_pad = 0
        self._batch_template = None
        self._build()

    # Largest gathered flat client stack (c_pad × F × 4 bytes) the "auto"
    # hop transport will materialize per device; beyond it hops fall back to
    # the O(block)-memory ring shifts.
    GATHER_BUDGET_BYTES = 1 << 30

    # Hop runs pad to a multiple of this many waves with identity no-op
    # segments, bounding the signature space (and hence trace + compile
    # count) while a no-op wave costs one skipped hop at runtime.
    FUSED_WAVE_BUCKET = 4

    # Smallest fleet for which ``shard_overlap="auto"`` takes the fused
    # round plane: below it per-op dispatch is cheap relative to the round
    # and the whole-round program only adds compile latency.
    FUSED_MIN_CLIENTS = 256

    def _hop_transport(self, params) -> str:
        """Resolve the fused-plane hop collective for this model size.

        ``"gather"`` moves each hop with ONE tiled ``all_gather`` over the
        combined mesh axes plus a local row-take — a single collective
        rendezvous per hop, the fast path whenever the gathered
        ``(c_pad, F)`` stack fits :data:`GATHER_BUDGET_BYTES` per device.
        ``"ring"`` is the per-shift ``ppermute`` decomposition (double
        buffered when ``km == 1``): kc rendezvous per hop but O(block)
        memory — the large-model path.
        """
        if self._transport is None:
            if self._transport_req != "auto":
                self._transport = self._transport_req
            else:
                # params: the GLOBAL (unstacked) pytree — F is its flat size.
                f = sum(int(np.prod(x.shape))
                        for x in jax.tree.leaves(params))
                gathered = 4 * self.c_pad * f
                self._transport = ("gather"
                                   if gathered <= self.GATHER_BUDGET_BYTES
                                   else "ring")
        return self._transport

    # -------------------------------------------------------- slot padding

    def _pad_mask(self, mask) -> np.ndarray:
        m = np.zeros(self.c_pad, dtype=bool)
        m[:self.c] = np.asarray(mask, dtype=bool)
        return m

    def _pad_perm(self, src_of_dst) -> np.ndarray:
        p = np.arange(self.c_pad, dtype=np.int64)
        p[:self.c] = np.asarray(src_of_dst, dtype=np.int64)
        return p

    def _pad_matrix(self, w: np.ndarray) -> np.ndarray:
        # Identity on the padding block: padded slots keep their content
        # and contribute weight 0 to every real slot's mixture.
        out = np.eye(self.c_pad, dtype=np.float32)
        out[:self.c, :self.c] = w
        return out

    def _pad_weights(self, w) -> np.ndarray:
        out = np.zeros(self.c_pad, dtype=np.float32)
        out[:self.c] = np.asarray(w, dtype=np.float32)
        return out

    # ------------------------------------------------------- compiled planes

    def _shmap(self, f, in_specs, out_specs):
        return jax.jit(jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def _build(self) -> None:
        axes = self._axes
        pc = P(axes)
        kc, km = self.kc, self.km
        nl, nl_hop = self.nl, self.nl_hop
        nchunks, mb = self.nchunks, self.mb
        D, mbh = self.fused_chunks, self.fused_mb
        if self._frozen:
            raise NotImplementedError(
                "the sharded engine takes no frozen tree; use the fleet")
        vstep = jax.vmap(self._one)

        def chunked_session_step(p, mom, batch, active, anchor):
            # Local block of nl clients, trained in nchunks microbatches so
            # activations/grads are O(mb) per device, not O(N).
            args = (p, mom, batch, active, anchor)
            if nchunks == 1:
                return vstep(*args)
            split = jax.tree.map(
                lambda x: x.reshape((nchunks, mb) + x.shape[1:]), args)
            out = jax.lax.map(lambda a: vstep(*a), split)
            return jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), out)

        # Overrides FleetExecutor._step: _session() is inherited unchanged.
        self._step = self._shmap(chunked_session_step,
                                 in_specs=(pc, pc, pc, pc, pc),
                                 out_specs=(pc, pc, pc))

        def session_local(params, steps):
            # Fused-plane session body: same math as FleetExecutor._session
            # but running *inside* shard_map on the local block.  Steps
            # whose active mask is empty on this device — signature
            # padding, ragged epochs — are skipped by a real branch: the
            # lax.cond sits outside the vmap, so a padded step costs one
            # predicate, not a training step.
            if not steps:
                return params
            mom = jax.tree.map(
                lambda p: jnp.zeros_like(p, jnp.float32), params)
            anchor = params
            for batch, active in steps:
                def run(carry, batch=batch, active=active):
                    p, m = carry
                    p2, m2, _ = chunked_session_step(p, m, batch, active,
                                                     anchor)
                    return p2, m2
                params, mom = jax.lax.cond(jnp.any(active), run,
                                           lambda carry: carry,
                                           (params, mom))
            return params

        self._local_session = session_local

        def shift_rows(x, send, recv):
            # x: (nl_hop, F) hop-layout rows; send/recv: (kc, nl_hop) local
            # routing tables.  kc ring shifts, trash row nl_hop for padding.
            out = jnp.zeros((nl_hop + 1,) + x.shape[1:], x.dtype)
            for shift in range(kc):
                buf = jnp.take(x, send[shift], axis=0)
                if shift:
                    buf = jax.lax.ppermute(
                        buf, CLIENT_AXIS,
                        [(s, (s + shift) % kc) for s in range(kc)])
                out = out.at[recv[shift]].set(buf)
            return out[:nl_hop]

        quant = self.quant

        def permute_local(params, send_all, recv_all):
            # Routing tables travel replicated ((kc, kc, nl_hop)); each ring
            # slot selects its row by mesh position.
            ic = jax.lax.axis_index(CLIENT_AXIS)
            send, recv = send_all[ic], recv_all[ic]
            if km == 1:
                if quant:
                    # int8 wire: ring-shift the packed codes and their
                    # scales instead of fp32 rows, decode at the
                    # destination (shift_rows is dtype-generic).
                    flat, spec = stack_ravel(params)
                    q, s = pack_rows(flat)
                    q = shift_rows(q, send, recv)
                    s = shift_rows(s, send, recv)
                    return stack_unravel(unpack_rows(q, s, flat.shape[1]),
                                         spec)
                return jax.tree.map(
                    lambda x: shift_rows(x, send, recv), params)
            # Hop layout: feature-split every leaf over "model" so one ring
            # shift moves F/km bytes per link.  After the all_to_all the
            # device holds the *contiguous* client rows of its ring slot
            # (row blocks concatenate in model-axis order, and the combined
            # linear device order is ic·km + im), which is exactly the
            # contiguity _permutation_tables assumes.
            flat, spec = stack_ravel(params)
            if quant:
                # A km-way feature split cuts across quantization row-
                # blocks, so the packed wire needs km == 1 (or the gather
                # transport, which moves whole rows); here the payload is
                # decoded locally — numerically identical hop, fp32 moves.
                flat = quant_roundtrip_rows(flat)
            f = flat.shape[1]
            fpad = (-f) % km
            if fpad:
                flat = jnp.pad(flat, ((0, 0), (0, fpad)))
            x = jax.lax.all_to_all(flat, MODEL_AXIS, split_axis=1,
                                   concat_axis=0, tiled=True)
            y = shift_rows(x, send, recv)
            y = jax.lax.all_to_all(y, MODEL_AXIS, split_axis=0,
                                   concat_axis=1, tiled=True)
            return stack_unravel(y[:, :f], spec)

        self._local_permute = permute_local
        self._sh_permute = self._shmap(permute_local,
                                       in_specs=(pc, P(), P()), out_specs=pc)

        def gather_permute_local(params, perm):
            # One-collective hop: tiled all_gather over the combined axes
            # reassembles the (c_pad, F) flat stack in global slot order
            # (device linear index ic·km + im matches the concatenation
            # order), then each device takes its own destination rows.  One
            # rendezvous per hop vs the ring's kc — the fast transport while
            # the gathered stack fits GATHER_BUDGET_BYTES.
            flat, spec = stack_ravel(params)
            d = jax.lax.axis_index(CLIENT_AXIS)
            if km > 1:
                d = d * km + jax.lax.axis_index(MODEL_AXIS)
            rows = jax.lax.dynamic_slice_in_dim(perm, d * nl, nl)
            if quant:
                # int8 wire: gather the packed codes + scales (whole client
                # rows, so blocks stay intact at any km), decode the taken
                # destination rows.
                q, s = pack_rows(flat)
                fq = jax.lax.all_gather(q, axes, axis=0, tiled=True)
                fs = jax.lax.all_gather(s, axes, axis=0, tiled=True)
                return stack_unravel(
                    unpack_rows(jnp.take(fq, rows, axis=0),
                                jnp.take(fs, rows, axis=0), flat.shape[1]),
                    spec)
            full = jax.lax.all_gather(flat, axes, axis=0, tiled=True)
            return stack_unravel(jnp.take(full, rows, axis=0), spec)

        self._local_permute_gather = gather_permute_local

        def chunked_permute_session(params, send_all, recv_all, steps):
            # Double-buffered fused hop (km == 1): rows are routed per
            # *destination chunk*; chunk j's scatter+train consumes only its
            # own buffers while chunk j+1's gathers read the pre-hop flat
            # block, so the backend can overlap j+1's collectives with j's
            # compute.  Concatenating the trained chunks restores slot order.
            ic = jax.lax.axis_index(CLIENT_AXIS)
            send, recv = send_all[ic], recv_all[ic]     # (D, kc, mbh)
            flat, spec = stack_ravel(params)
            if quant:
                # int8 wire: pack the pre-hop block once; each chunk then
                # routes its slice of codes + scales through the same
                # double-buffered shifts and decodes on arrival.
                qf, sf = pack_rows(flat)
            chunks = []
            for j in range(D):
                if quant:
                    outq = jnp.zeros((mbh + 1, qf.shape[1]), qf.dtype)
                    outs = jnp.zeros((mbh + 1, sf.shape[1]), sf.dtype)
                    for shift in range(kc):
                        bq = jnp.take(qf, send[j, shift], axis=0)
                        bs = jnp.take(sf, send[j, shift], axis=0)
                        if shift:
                            links = [(s, (s + shift) % kc)
                                     for s in range(kc)]
                            bq = jax.lax.ppermute(bq, CLIENT_AXIS, links)
                            bs = jax.lax.ppermute(bs, CLIENT_AXIS, links)
                        outq = outq.at[recv[j, shift]].set(bq)
                        outs = outs.at[recv[j, shift]].set(bs)
                    chunk = stack_unravel(
                        unpack_rows(outq[:mbh], outs[:mbh], flat.shape[1]),
                        spec)
                else:
                    out = jnp.zeros((mbh + 1, flat.shape[1]), flat.dtype)
                    for shift in range(kc):
                        buf = jnp.take(flat, send[j, shift], axis=0)
                        if shift:
                            buf = jax.lax.ppermute(
                                buf, CLIENT_AXIS,
                                [(s, (s + shift) % kc) for s in range(kc)])
                        out = out.at[recv[j, shift]].set(buf)
                    chunk = stack_unravel(out[:mbh], spec)
                if steps:
                    mom = jax.tree.map(
                        lambda p: jnp.zeros_like(p, jnp.float32), chunk)
                    anchor = chunk
                    for batch, active in steps:
                        bch = jax.tree.map(
                            lambda x: x[j * mbh:(j + 1) * mbh], batch)
                        act = active[j * mbh:(j + 1) * mbh]

                        def run(carry, bch=bch, act=act, anchor=anchor):
                            p, m = carry
                            p2, m2, _ = vstep(p, m, bch, act, anchor)
                            return p2, m2
                        chunk, mom = jax.lax.cond(
                            jnp.any(act), run, lambda carry: carry,
                            (chunk, mom))
                chunks.append(chunk)
            return jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *chunks)

        self._local_permute_session = chunked_permute_session

        def mix_local(params, wt_local):
            # wt_local: this device's (nl, C_pad) block of Wᵀ — the kernel
            # data plane computes the partial products over local source
            # slots ((C_pad, ...) fp32 per leaf: partials stay fp32 across
            # the collective), then psum_scatter reduces them back to
            # owners.  Scattering over "clients" then "model" lands row
            # block (ic·km + im)·nl — the combined-order train layout.
            part = kernel_ops.mix_aggregate_tree(params, wt_local.T,
                                                 keep_float32=True)

            def scatter(x, orig):
                out = jax.lax.psum_scatter(x, CLIENT_AXIS,
                                           scatter_dimension=0, tiled=True)
                if km > 1:
                    out = jax.lax.psum_scatter(out, MODEL_AXIS,
                                               scatter_dimension=0,
                                               tiled=True)
                return out.astype(orig.dtype)
            return jax.tree.map(scatter, part, params)

        self._local_mix = mix_local
        self._sh_mix = self._shmap(mix_local, in_specs=(pc, pc),
                                   out_specs=pc)

        def agg_local(payload, w_local):
            # Eq. (11) as a masked psum over the combined axes: dropped,
            # churned and padding slots carry zero weight, so their rows
            # contribute nothing to the reduction.
            part = kernel_ops.mix_aggregate_tree(
                payload, w_local.reshape(1, -1), collapse=True,
                keep_float32=True)

            def reduce(x, orig):
                return jax.lax.psum(x, axes).astype(orig.dtype)
            return jax.tree.map(reduce, part, payload)

        self._local_agg = agg_local
        self._sh_agg = self._shmap(agg_local, in_specs=(pc, pc),
                                   out_specs=P())

        def bcast_local(g):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (nl,) + x.shape), g)

        self._local_bcast = bcast_local
        self._sh_bcast = self._shmap(bcast_local, in_specs=P(), out_specs=pc)

    def _sh_stc(self, sparsity: float):
        fn = self._stc_cache.get(sparsity)
        if fn is None:
            pc = P(self._axes)

            def stc_tree(params, ref, mask):
                return masked_stc_compress(params, ref, mask, sparsity)
            fn = self._shmap(stc_tree, in_specs=(pc, P(), pc), out_specs=pc)
            self._stc_cache[sparsity] = fn
        return fn

    # ------------------------- primitive overrides (round loop inherited)

    def capture_slots(self, slots: Params | None):
        # Padding slots are an executor-internal placement detail — strip
        # them so checkpoints are executor-portable.
        if slots is None:
            return None
        host = jax.device_get(slots)
        if self.c_pad == self.c:
            return host
        return jax.tree.map(lambda x: x[:self.c], host)

    def adopt_slots(self, saved):
        # Restored slot state must land client-sharded (zero-filled padding
        # rows) — the shard_map planes expect the leading axis on the mesh.
        sh = jax.sharding.NamedSharding(self.mesh, P(self._axes))

        def place(x):
            x = np.asarray(x)
            if self.c_pad != self.c:
                pad = np.zeros((self.c_pad - self.c,) + x.shape[1:],
                               x.dtype)
                x = np.concatenate([x, pad], axis=0)
            return jax.device_put(jnp.asarray(x), sh)
        return jax.tree.map(place, saved)

    def _broadcast(self, global_params: Params, num_slots: int) -> Params:
        return self._sh_bcast(global_params)

    def _session(self, params: Params, mask: np.ndarray) -> Params:
        # Every (padded) slot, sharded over the mesh: a compact subset of
        # the slots would not be.
        if not mask.any():
            return params
        steps, actives = self._draw_session(self._pad_mask(mask))
        return self._full_session(params, steps, actives)

    def _permute(self, params: Params, op: PermuteOp) -> Params:
        send, recv = _permutation_tables(self._pad_perm(op.src_of_dst),
                                         self.kc)
        return self._sh_permute(params, jnp.asarray(send),
                                jnp.asarray(recv))

    def _mix(self, params: Params, op: MixOp, num_slots: int) -> Params:
        wt = np.ascontiguousarray(
            self._pad_matrix(op.matrix(num_slots)).T)
        return self._sh_mix(params, jnp.asarray(wt))

    def _masked_stc(self, params: Params, ref: Params, mask: np.ndarray,
                    sparsity: float) -> Params:
        return self._sh_stc(sparsity)(params, ref,
                                      jnp.asarray(self._pad_mask(mask)))

    def _aggregate(self, payload: Params, w: jax.Array) -> Params:
        return self._sh_agg(payload,
                            jnp.asarray(self._pad_weights(np.asarray(w))))

    # ------------------------------------------------------ fused round plane

    def _build_fused(self, segs: tuple, persistent_in: bool,
                     stc_delta: bool, sparsity: float, transport: str):
        pc = P(self._axes)
        km, D = self.km, self.fused_chunks
        session = self._local_session
        gather = transport == "gather"

        in_specs: list = [P()]                   # global params (replicated)
        if persistent_in:
            in_specs.append(pc)                  # carried slot state
        for seg in segs:
            if seg[0] == "train":
                in_specs += [pc, pc] * seg[1]    # (batch, active) per step
            elif seg[0] == "perm":
                if seg[2]:
                    in_specs.append(pc)          # compress-source mask
                # gather: padded permutation; ring: send/recv routing
                # tables — replicated either way
                in_specs += [P()] if gather else [P(), P()]
                in_specs += [pc, pc] * seg[1]
            else:                                # mix
                in_specs.append(pc)              # Wᵀ row block
        if stc_delta:
            in_specs.append(pc)                  # agg compress mask
        in_specs.append(pc)                      # agg weights

        def fused(g, *rest):
            it = iter(rest)
            params = next(it) if persistent_in else self._local_bcast(g)
            ref = g
            for seg in segs:
                if seg[0] == "train":
                    steps = [(next(it), next(it)) for _ in range(seg[1])]
                    params = session(params, steps)
                elif seg[0] == "perm":
                    cmask = next(it) if seg[2] else None
                    route = (next(it),) if gather else (next(it), next(it))
                    steps = [(next(it), next(it)) for _ in range(seg[1])]
                    if cmask is not None:
                        params = masked_stc_compress(params, ref, cmask,
                                                     sparsity)
                    if gather:
                        params = self._local_permute_gather(params, *route)
                        params = session(params, steps)
                    elif km == 1 and D > 1:
                        params = self._local_permute_session(
                            params, *route, steps)
                    else:
                        params = self._local_permute(params, *route)
                        params = session(params, steps)
                else:
                    params = self._local_mix(params, next(it))
            wmask = next(it) if stc_delta else None
            w_local = next(it)
            payload = (masked_stc_compress(params, ref, wmask, sparsity)
                       if stc_delta else params)
            return self._local_agg(payload, w_local), params

        return self._shmap(fused, in_specs=tuple(in_specs),
                           out_specs=(P(), pc))

    def _run_round_fused(self, sched: RoundSchedule, global_params: Params,
                         slots: Params | None
                         ) -> tuple[Params, Params | None]:
        persistent_in = bool(sched.persistent and slots is not None)
        transport = self._hop_transport(global_params)
        # Pass 1 — draw every session in schedule order (batch-stream
        # parity with the op-by-op loop) and settle the round's uniform
        # step count before any segment is emitted: padding to a running
        # max mid-walk would leave earlier segments shorter and the
        # signature ragged again.
        drawn: list = []
        for op in sched.ops:
            if isinstance(op, (TrainOp, PermuteOp)):
                steps, actives = self._draw_session(
                    self._pad_mask(op.train_mask))
                if steps and self._batch_template is None:
                    self._batch_template = jax.tree.map(jnp.zeros_like,
                                                        steps[0])
                self._nb_pad = max(self._nb_pad, len(steps))
                drawn.append((op, list(zip(steps, actives))))
            elif isinstance(op, MixOp):
                drawn.append((op, None))
            else:
                raise TypeError(f"unknown op {type(op).__name__}")

        nb = self._nb_pad
        dead = jnp.zeros(self.c_pad, dtype=bool)

        def pad_steps(pairs):
            pairs += [(self._batch_template, dead)] * (nb - len(pairs))
            return pairs

        def route_args(perm):
            if transport == "gather":
                return [jnp.asarray(perm)]
            if self.km == 1 and self.fused_chunks > 1:
                send, recv = _chunked_permutation_tables(
                    perm, self.kc, self.fused_chunks)
            else:
                send, recv = _permutation_tables(perm, self.kc)
            return [jnp.asarray(send), jnp.asarray(recv)]

        # Pass 2 — emit segments, bucketing every hop run (see docstring).
        segs: list = []
        args: list = []
        pend = 0                 # open hop-run length
        pend_compress = False

        def close_run():
            nonlocal pend
            npad = (-pend) % self.FUSED_WAVE_BUCKET if pend else 0
            for _ in range(npad):
                if pend_compress:
                    args.append(dead)
                args.extend(route_args(np.arange(self.c_pad,
                                                 dtype=np.int64)))
                for _ in range(nb):
                    args.extend((self._batch_template, dead))
                segs.append(("perm", nb, pend_compress))
            pend = 0

        for op, pairs in drawn:
            if isinstance(op, TrainOp):
                close_run()
                pairs = pad_steps(pairs)
                segs.append(("train", len(pairs)))
                for b, a in pairs:
                    args.extend((b, a))
            elif isinstance(op, PermuteOp):
                compress = bool(op.compress)
                if pend and compress != pend_compress:
                    close_run()
                pend_compress = compress
                pend += 1
                if compress:
                    args.append(jnp.asarray(
                        self._pad_mask(op.compress_src_mask())))
                args.extend(route_args(self._pad_perm(op.src_of_dst)))
                pairs = pad_steps(pairs)
                segs.append(("perm", len(pairs), compress))
                for b, a in pairs:
                    args.extend((b, a))
            else:
                close_run()
                segs.append(("mix",))
                wt = np.ascontiguousarray(
                    self._pad_matrix(op.matrix(sched.num_slots)).T)
                args.append(jnp.asarray(wt))
        close_run()
        wvec = sched.slot_weights()
        stc_delta = sched.agg_mode == "stc_delta"
        if stc_delta:
            args.append(jnp.asarray(self._pad_mask(wvec > 0)))
        args.append(jnp.asarray(self._pad_weights(
            (wvec / wvec.sum()).astype(np.float32))))

        key = (tuple(segs), persistent_in, stc_delta,
               float(sched.stc_sparsity), transport)
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = self._build_fused(tuple(segs), persistent_in, stc_delta,
                                   float(sched.stc_sparsity), transport)
            self._fused_cache[key] = fn
        if persistent_in:
            new_global, params = fn(global_params, slots, *args)
        else:
            new_global, params = fn(global_params, *args)
        return new_global, (params if sched.persistent else None)

    def run_round(self, sched: RoundSchedule, global_params: Params,
                  slots: Params | None) -> tuple[Params, Params | None]:
        # The mesh/tables were built for cfg.num_clients slots.
        assert sched.num_slots == self.cfg.num_clients, \
            (sched.num_slots, self.cfg.num_clients)
        if self.overlap:
            return self._traced("fl.exec.fused", self._run_round_fused,
                                sched, global_params, slots)
        return super().run_round(sched, global_params, slots)


def make_executor(name: str, loss_fn: Callable, local_update: Callable,
                  client_batches: Sequence[Callable], cfg, spec):
    """Build the executor ``name`` (a resolved ``spec.mode``, or the async
    plane's inner data plane); the sharded plane's knobs come from the
    :class:`~repro.fl.engine.EngineSpec` ``spec``."""
    if name == "host":
        return HostExecutor(local_update, client_batches, cfg)
    if name == "fleet":
        return FleetExecutor(loss_fn, client_batches, cfg)
    if name == "sharded":
        return ShardedFleetExecutor(
            loss_fn, client_batches, cfg,
            mesh_model_axis=spec.mesh_model_axis,
            shard_microbatch=spec.shard_microbatch,
            shard_overlap=spec.shard_overlap,
            shard_hop_transport=spec.shard_hop_transport)
    raise ValueError(f"unknown executor {name!r}; expected one of "
                     f"{EXECUTORS}")
