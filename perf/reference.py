"""The plain reference of a communication round, and the comparison that
decides ``correct``.  It imports nothing of the program.

What the reference takes: the configuration's plain model (``<config>_ref``),
the traffic the benchmark generated, the seeds it handed the program, the
round schedules the program's scheduler returned for the first three
rounds and, where the plain model defines ``frozen``, the frozen tree the
harness built once and handed the program too.  That tree is an argument
of every call that runs the model (``model_loss``), never a constant of a
compiled program.  A schedule is an answer of the program's control
plane, so it is checked before it is followed, by the strategy's own file
(``perf/strategies/<strategy>.py``) on the channels the scenario's file
(``perf/worlds/<scenario>.py``) draws from the round's seed:

* every hop is re-derived from the slot permutations and train masks and
  must be feasible on the reference's channel (Eqs. 12-14);
* the aggregation weights must be the reference's Eq.-11 weights;
* the wire events the reference derives are charged with its own Eq.-15
  arithmetic (5G numerology 0: 1 ms sub-frames of 180 kHz), and the
  resulting ledger must equal the program's.

The data plane is then replayed in plain JAX: each client's batches in the
order its loader's spec gives (epoch k of client i shuffles with
``default_rng(loader_seed + 1000 i + k)``), SGD with heavy-ball momentum
restarted every session and per-client global-norm clipping, the hops as
slot permutations, and the Eq.-11 weighted mean.  The first local step of
round 1 is computed on its own too: the momentum after it is each client's
first gradient as the optimizer gets it.
"""
from __future__ import annotations

import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

# Sec. VI-A radio constants.
BETA0_DB, D0_M, KAPPA = -30.0, 1.0, 3.0
TX_POWER_DBM, NOISE_PSD_DBM_HZ, PRB_HZ = 23.0, -174.0, 180e3
SUBFRAME_S = 1e-3
GAMMA_FLOOR = 0.05          # spectral-efficiency floor before charging
CLIP_NORM = 10.0            # per-client gradient clipping of a session


# ------------------------------------------------------------ radio model

def pathloss(dist):
    """Eq. 12's log-distance large-scale gain."""
    return 10 ** ((BETA0_DB - 10.0 * KAPPA * np.log10(
        np.maximum(dist, D0_M) / D0_M)) / 10.0)


def efficiency(gains):
    """Eqs. 13-14: spectral efficiency of a link with gain ``gains`` over
    one resource block."""
    tx_w = 10 ** ((TX_POWER_DBM - 30.0) / 10.0)
    noise_w = 10 ** ((NOISE_PSD_DBM_HZ - 30.0) / 10.0) * PRB_HZ
    return np.log2(1.0 + gains * tx_w / (noise_w + 0.0))


@dataclasses.dataclass
class Ledger:
    subframes: int = 0
    transmitted_models: int = 0
    transmitted_bits: float = 0.0
    uplink_models: int = 0
    downlink_models: int = 0

    def _sf(self, bits, gamma):
        rate = gamma * PRB_HZ
        return int(np.ceil(bits / (rate * SUBFRAME_S)))

    def d2d(self, bits, gamma):
        self.subframes += self._sf(bits, gamma)
        self.transmitted_models += 1
        self.transmitted_bits += bits

    def uplink(self, bits, gamma):
        self.subframes += self._sf(bits, max(gamma, 1e-9))
        self.uplink_models += 1
        self.transmitted_models += 1
        self.transmitted_bits += bits

    def downlink(self, bits, gamma):
        self.subframes += self._sf(bits, max(gamma, 1e-9))
        self.downlink_models += 1

    def gap(self, prog: dict) -> float:
        return float(max(abs(getattr(self, k) - prog[k])
                         for k in dataclasses.asdict(self)))


# --------------------------------------------------------- schedule check

@dataclasses.dataclass
class RoundPlan:
    """What the reference follows for one round, after checking it."""
    sessions: list          # per train step group: (perm or None, mask)
    weights: np.ndarray     # (N,) Eq.-11 slot weights
    faults: list            # what the check found wrong


def strategy_ref(name: str):
    """The schedule reference of one strategy, ``perf/strategies/<name>.py``:
    ``d2d_rounds(mix)``, the D2D channel draws a round of it makes, and
    ``plan(sched, mix, sizes, up, d2d, bits, ledger) -> RoundPlan``, which
    checks the round's ops and wire events, charges the uplinks and hops
    to the ledger and returns the sessions and Eq.-11 weights to follow."""
    return importlib.import_module(f"perf.strategies.{name}")


def world_ref(name: str):
    """The channel draws of one scenario, ``perf/worlds/<name>.py``:
    ``round_channels(topology_seed, t, n, d2d_rounds) -> (up, d2d)``."""
    return importlib.import_module(f"perf.worlds.{name}")


def check_schedule(sched, mix: dict, sizes: np.ndarray, up: np.ndarray,
                   d2d: list, bits: float, ledger: Ledger) -> RoundPlan:
    """One round's schedule against the strategy's reference: the global
    model's downlink, then the strategy's ops, then the weights."""
    ledger.downlink(bits, float(np.median(up)))
    plan = strategy_ref(mix["strategy"]).plan(sched, mix, sizes, up, d2d,
                                              bits, ledger)
    prog_w = np.zeros(len(plan.weights), np.float64)
    for slot, w in sched.agg:
        prog_w[int(slot)] += float(w)
    if not np.array_equal(prog_w, plan.weights):
        plan.faults.append("aggregation weights are not the chain sizes")
    return plan


# ------------------------------------------------------------- data plane

def epoch_indices(loader_seed: int, client: int, epoch: int, rows: int,
                  batch: int) -> np.ndarray:
    """(batches, batch) row indices of one client epoch (within its shard)."""
    perm = np.random.default_rng(loader_seed + 1000 * client
                                 + epoch).permutation(rows)
    nb = max(1, rows // batch)
    out = []
    for i in range(nb):
        idx = perm[i * batch:(i + 1) * batch]
        if len(idx) < batch:
            idx = np.concatenate([idx, np.resize(perm, batch - len(idx))])
        out.append(idx)
    return np.stack(out)


def model_loss(ref, conf: dict, dtype):
    """``(q, x, y, frozen) -> loss`` of the configuration's reference: its
    ``loss(q, x, y, conf, dtype, frozen)`` where it defines ``frozen``,
    and ``loss(q, x, y, conf, dtype)`` otherwise."""
    if hasattr(ref, "frozen"):
        return lambda q, x, y, fz: ref.loss(q, x, y, conf, dtype, fz)
    return lambda q, x, y, fz: ref.loss(q, x, y, conf, dtype)


class Trainer:
    """Client sessions in plain JAX, over blocks of slots.  The frozen
    tree (None without one) is an argument of the step, shared by every
    slot, so no compiled program holds a copy of it."""

    def __init__(self, ref, conf: dict, mix: dict, compute_dtype,
                 param_dtype, block: int, frozen=None):
        lr, mom = float(mix["lr"]), float(mix["momentum"])
        self.block = block
        self.param_dtype = jnp.dtype(param_dtype)
        self.frozen = frozen
        loss = model_loss(ref, conf, compute_dtype)

        def one(p, mu, x, y, active, fz):
            g = jax.grad(lambda q: loss(q, x, y, fz))(p)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                                for v in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, CLIP_NORM / jnp.maximum(norm, 1e-9))
            mu2 = jax.tree.map(lambda u, v: mom * u + v.astype(jnp.float32)
                               * scale, mu, g)
            p2 = jax.tree.map(lambda q, u: (q.astype(jnp.float32) - lr * u)
                              .astype(q.dtype), p, mu2)
            sel = lambda a, b: jnp.where(active, a, b)     # noqa: E731
            return jax.tree.map(sel, p2, p), jax.tree.map(sel, mu2, mu)

        self.step = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 0, 0, None)),
                            donate_argnums=(0, 1))

    def session(self, blocks: list, xs: np.ndarray, ys: np.ndarray,
                active: np.ndarray) -> list:
        """``xs`` (steps, C, B, ...), ``active`` (steps, C)."""
        out, lo = [], 0
        for blk in blocks:
            c = jax.tree.leaves(blk)[0].shape[0]
            sl = slice(lo, lo + c)
            lo += c
            if not active[:, sl].any():
                out.append(blk)
                continue
            p = jax.tree.map(jnp.copy, blk)
            mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
            for j in range(xs.shape[0]):
                if active[j, sl].any():
                    p, mu = self.step(p, mu, jnp.asarray(xs[j, sl]),
                                      jnp.asarray(ys[j, sl]),
                                      jnp.asarray(active[j, sl]),
                                      self.frozen)
            out.append(p)
            del mu
        return out

    def first_momentum(self, blocks: list, xs: np.ndarray,
                       ys: np.ndarray) -> list:
        """The momentum after one step from zero at every slot (host
        blocks): each client's clipped gradient on its batch ``xs[c]``."""
        out, lo = [], 0
        for blk in blocks:
            c = jax.tree.leaves(blk)[0].shape[0]
            p = jax.tree.map(jnp.copy, blk)
            mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
            _, mu = self.step(p, mu, jnp.asarray(xs[lo:lo + c]),
                              jnp.asarray(ys[lo:lo + c]), jnp.ones(c, bool),
                              self.frozen)
            out.append(jax.device_get(mu))
            lo += c
        return out


def _permute(blocks: list, perm: np.ndarray) -> list:
    if all(jax.tree.leaves(b)[0].shape[0] == 1 for b in blocks):
        return [blocks[int(i)] for i in perm]
    whole = (blocks[0] if len(blocks) == 1 else
             jax.tree.map(lambda *a: jnp.concatenate(a), *blocks))
    whole = jax.tree.map(lambda a: jnp.take(a, jnp.asarray(perm), axis=0),
                         whole)
    return _split(whole, jax.tree.leaves(blocks[0])[0].shape[0])


def _split(whole, block: int) -> list:
    c = jax.tree.leaves(whole)[0].shape[0]
    return [jax.tree.map(lambda a: a[i:i + block], whole)
            for i in range(0, c, block)]


def _aggregate(blocks: list, w: np.ndarray, dtype):
    w = jnp.asarray((w / w.sum()).astype(np.float32))
    acc, lo = None, 0
    for blk in blocks:
        c = jax.tree.leaves(blk)[0].shape[0]
        part = jax.tree.map(
            lambda a: jnp.einsum("c,c...->...", w[lo:lo + c],
                                 a.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST), blk)
        acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
        lo += c
    return jax.tree.map(lambda a: a.astype(dtype), acc)


def _fleet(g, n: int, block: int) -> list:
    return _split(jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                               g), block)


def first_gradient(mix: dict, traffic, loader_seed: int, params0,
                   trainer: Trainer) -> list:
    """Every client's first gradient as the optimizer gets it: the momentum
    after the first local step of round 1, at the initial model, on the
    first batch of the client's first epoch (host blocks of slots)."""
    n, batch = int(mix["clients"]), int(mix["batch_size"])
    rows = np.stack([traffic.part.indices[c][epoch_indices(
        loader_seed, c, 0, len(traffic.part.indices[c]), batch)[0]]
        for c in range(n)])
    g = jax.tree.map(lambda a: a.astype(trainer.param_dtype), params0)
    return trainer.first_momentum(_fleet(g, n, trainer.block),
                                  traffic.train.x[rows],
                                  traffic.train.y[rows])


def replay(ref, conf: dict, mix: dict, traffic, loader_seed: int,
           params0, plans: list, trainer: Trainer) -> list:
    """Global params after each planned round (host copies)."""
    n, batch = int(mix["clients"]), int(mix["batch_size"])
    sizes = [len(ix) for ix in traffic.part.indices]
    epochs = np.zeros(n, np.int64)
    g = jax.tree.map(lambda a: a.astype(trainer.param_dtype), params0)
    out = []
    for plan in plans:
        blocks = _fleet(g, n, trainer.block)
        for perm, mask in plan.sessions:
            if perm is not None:
                blocks = _permute(blocks, perm)
            idx = {}
            for c in np.flatnonzero(mask):
                idx[c] = epoch_indices(loader_seed, int(c), int(epochs[c]),
                                       sizes[c], batch)
                epochs[c] += 1
            steps = max(len(v) for v in idx.values())
            active = np.zeros((steps, n), bool)
            rows = np.zeros((steps, n, batch), np.int64)
            for c, ix in idx.items():
                active[:len(ix), c] = True
                rows[:len(ix), c] = traffic.part.indices[c][ix]
            blocks = trainer.session(blocks, traffic.train.x[rows],
                                     traffic.train.y[rows], active)
        g = _aggregate(blocks, plan.weights, trainer.param_dtype)
        del blocks
        out.append(jax.device_get(g))
    return out


def test_loss(ref, conf: dict, params, x: np.ndarray, y: np.ndarray,
              block: int, dtype=jnp.float32, frozen=None) -> float:
    f = jax.jit(model_loss(ref, conf, dtype))
    total = 0.0
    for i in range(0, len(y), block):
        total += float(f(params, jnp.asarray(x[i:i + block]),
                         jnp.asarray(y[i:i + block]), frozen)) \
            * len(y[i:i + block])
    return total / len(y)


# ------------------------------------------------------------- comparison

def leaf_change_norms(after, before) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(a, np.float64).ravel()
                                    - np.asarray(b, np.float64).ravel())
                     for a, b in zip(jax.tree.leaves(after),
                                     jax.tree.leaves(before))])


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """Worst leaf's gap between the two norms, against the larger of that
    leaf's reference norm and the median leaf's."""
    med = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(gaps[keep]))


def grad_norms(side: list | None, ref: list) -> tuple:
    """Per leaf, over every slot: the norm of the first gradient on each
    side, and the norm of their difference.  ``side`` and ``ref`` are lists
    of host stacked trees (blocks of slots); ``None`` is a side on which
    nothing moved."""
    leaves = [list(zip(*[jax.tree.leaves(b) for b in blocks]))
              if blocks is not None else None for blocks in (side, ref)]
    n_side, n_ref, diff = [], [], []
    for i, parts in enumerate(leaves[1]):
        r = np.concatenate(parts).astype(np.float64)
        s_ = (np.concatenate(leaves[0][i]).astype(np.float64)
              if side is not None else np.zeros_like(r))
        n_side.append(np.linalg.norm(s_.ravel()))
        n_ref.append(np.linalg.norm(r.ravel()))
        diff.append(np.linalg.norm((s_ - r).ravel()))
    return np.array(n_side), np.array(n_ref), np.array(diff)


@dataclasses.dataclass
class Reading:
    """One side's outputs over the first three rounds."""
    losses: list                    # test loss after rounds 1..3
    grad1: list | None              # first gradient at every slot (blocks)
    norms3: np.ndarray              # per-leaf change after round 3


def compare(side: Reading, ref: Reading) -> dict:
    """The numbers of one side against the reference.  Leaves whose first
    gradient in the reference is under a thousandth of the median leaf's
    move by rounding alone and are left out of the gradient and change
    numbers."""
    n_side, n_ref, diff = grad_norms(side.grad1, ref.grad1)
    keep = n_ref >= 1e-3 * np.median(n_ref)
    med = float(np.median(n_ref[keep]))
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(side.losses, ref.losses)),
        "grad_gap": norm_gap(n_side, n_ref, keep),
        "grad_diff": float(np.max((diff / np.maximum(n_ref, med))[keep])),
        "change_gap": norm_gap(side.norms3, ref.norms3, keep),
        "leaves_left_out": int((~keep).sum()),
    }


def reading_of(ref_mod, conf, traffic, params0, globals3: list,
               grad1: list, eval_block: int,
               dtype=jnp.float32, frozen=None) -> Reading:
    losses = [test_loss(ref_mod, conf, g, traffic.test_x, traffic.test_y,
                        eval_block, dtype, frozen) for g in globals3]
    return Reading(losses=losses, grad1=grad1,
                   norms3=leaf_change_norms(globals3[-1], params0))


def model_bits(params) -> float:
    return 32.0 * sum(math.prod(a.shape) for a in jax.tree.leaves(params))
