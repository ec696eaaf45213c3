"""Degree-of-Learning (DoL) and IID-distance primitives of FedDif.

Implements Section III-B of the paper:

* **DSI** (data state information), Eq. before (2): a client's per-class data
  fraction ``d_i`` — a point on the probability simplex ``Δ^C``.
* **DoL** update, Eq. (2): the data-size-weighted running mixture of the DSIs
  of every client in a model's diffusion sub-chain.
* **IID distance**, Eq. (4)/(B.1): the distance of the DoL from the uniform
  distribution ``U = 1/C``.  The paper instantiates the Wasserstein-1 bound
  with the Euclidean norm (Eq. B.1); Appendix-C Scenario 2 also evaluates
  KL divergence and Jensen–Shannon divergence — all three are provided here.
* **Optimal DSI** of Lemma 1 (Eq. 29) and the feasibility bound of
  Corollary 1 (Eq. A.16).
* **Closed-form real-world IID distance** of Lemma 2 (Eq. 30).

The primitives and :class:`PlannerState` are ``jax.numpy`` on small
``(C,)``/``(N, C)`` arrays, so they run inside the jitted planner.
:class:`DiffusionState` is the host's mutable bookkeeping: it folds Eq. (2) in
NumPy float32 through the same body as :func:`update_dol`, with no device call.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

__all__ = [
    "DiffusionState",
    "PlannerState",
    "uniform_dol",
    "dsi_from_counts",
    "update_dol",
    "iid_distance",
    "iid_distance_candidates",
    "optimal_dsi",
    "min_feasible_data_size",
    "closed_form_iid_distance",
    "entropy",
]


def uniform_dol(num_classes: int, dtype=jnp.float32) -> Array:
    """``U = (1/C)·1`` — DoL of a model trained on perfectly IID data."""
    return jnp.full((num_classes,), 1.0 / num_classes, dtype=dtype)


def dsi_from_counts(counts: Array) -> Array:
    """DSI vector from per-class sample counts: ``d[c] = n_c / Σ n``.

    Accepts a trailing class axis; broadcasts over leading (client) axes.
    Degenerate all-zero counts map to the uniform simplex point (an empty
    client is "IID by vacuity" and contributes nothing anyway, because the
    DoL update weights by data size).
    """
    counts = jnp.asarray(counts, jnp.float32)
    total = jnp.sum(counts, axis=-1, keepdims=True)
    c = counts.shape[-1]
    return jnp.where(total > 0, counts / jnp.maximum(total, 1.0), 1.0 / c)


def _fold_dol(xp, dol, chain_size, dsi, data_size):
    """Eq. (2) in the array module ``xp`` (``jnp`` or ``np``), in float32."""
    chain_size = xp.asarray(chain_size, xp.float32)
    data_size = xp.asarray(data_size, xp.float32)
    new_size = chain_size + data_size
    num = chain_size[..., None] * dol + data_size[..., None] * dsi
    new_dol = num / xp.maximum(new_size[..., None], np.float32(1.0))
    # A model that has never trained (chain 0) adopts the client's DSI.
    return new_dol, new_size


def update_dol(dol: Array, chain_size: Array, dsi: Array, data_size: Array
               ) -> tuple[Array, Array]:
    """Eq. (2): fold one client's data into a model's DoL.

    ``ψ_k = (D_{k-1}·ψ_{k-1} + D_i·d_i) / (D_{k-1} + D_i)``

    Returns ``(new_dol, new_chain_size)``.  Broadcasts over leading axes so a
    whole fleet of models can be updated in one call.
    """
    return _fold_dol(jnp, dol, chain_size, dsi, data_size)


def _w1_norm(p: Array, num_classes: int) -> Array:
    """Paper's Eq. (B.1) instantiation: ``‖ψ − U‖₂``."""
    return jnp.linalg.norm(p - 1.0 / num_classes, axis=-1)


def _w1_true(p: Array, num_classes: int) -> Array:
    """True Wasserstein-1 on the ordered class line (CDF L1 distance).

    The paper *defines* IID distance via W1 (Eq. 3) but evaluates the
    Euclidean form (Eq. B.1).  We expose the genuine transport distance as
    well — used in tests to show both orderings agree on simplex mixtures.
    """
    u = jnp.full_like(p, 1.0 / num_classes)
    return jnp.sum(jnp.abs(jnp.cumsum(p - u, axis=-1)), axis=-1)


def _kld(p: Array, num_classes: int) -> Array:
    """KL(ψ ‖ U) — Appendix C, Scenario 2."""
    eps = 1e-12
    pc = jnp.clip(p, eps, 1.0)
    return jnp.sum(pc * (jnp.log(pc) - jnp.log(1.0 / num_classes)), axis=-1)


def _jsd(p: Array, num_classes: int) -> Array:
    """Jensen–Shannon divergence to uniform — Appendix C, Scenario 2."""
    eps = 1e-12
    u = 1.0 / num_classes
    m = 0.5 * (p + u)
    pc = jnp.clip(p, eps, 1.0)
    mc = jnp.clip(m, eps, 1.0)
    t1 = jnp.sum(pc * (jnp.log(pc) - jnp.log(mc)), axis=-1)
    t2 = jnp.sum(u * (jnp.log(u) - jnp.log(mc)), axis=-1)
    return 0.5 * (t1 + t2)


_DISTANCES = {
    "w1_norm": _w1_norm,   # the paper's default (Eq. B.1)
    "w1_true": _w1_true,
    "kld": _kld,
    "jsd": _jsd,
}


def iid_distance(dol: Array, metric: str = "w1_norm") -> Array:
    """IID distance ``δ(ψ) = dist(ψ, U)`` with a trailing class axis."""
    fn = _DISTANCES[metric]
    return fn(jnp.asarray(dol, jnp.float32), dol.shape[-1])


def iid_distance_candidates(dol: Array, chain_size: Array, dsi: Array,
                            data_size: Array, metric: str = "w1_norm"
                            ) -> Array:
    """Candidate IID distances (Sec. III-B "candidates of IID distance
    reporting"): for every (model m, client i) pair, the IID distance the
    model *would* have after client i trains it.

    Args:
      dol:        (M, C) current DoLs.
      chain_size: (M,)   current chain data sizes ``D_{P_{k-1}}``.
      dsi:        (N, C) client DSIs.
      data_size:  (N,)   client data sizes.

    Returns: (M, N) candidate IID distance matrix.
    """
    dol = jnp.asarray(dol, jnp.float32)[:, None, :]          # (M,1,C)
    chain = jnp.asarray(chain_size, jnp.float32)[:, None]    # (M,1)
    dsi = jnp.asarray(dsi, jnp.float32)[None, :, :]          # (1,N,C)
    size = jnp.asarray(data_size, jnp.float32)[None, :]      # (1,N)
    cand, _ = update_dol(dol, chain, dsi, size)
    return iid_distance(cand, metric)


def optimal_dsi(dol: Array, chain_size: Array, data_size: Array) -> Array:
    """Lemma 1 / Eq. (29): the DSI a model *wants* from its next trainer.

    ``d*[c] = (D_{P_k}/C − D_{P_{k-1}}·ψ_{k-1}[c]) / D_i`` with
    ``D_{P_k} = D_{P_{k-1}} + D_i``.  May leave the simplex when ``D_i`` is
    below the Corollary-1 bound; callers clip when sampling.
    """
    dol = jnp.asarray(dol, jnp.float32)
    chain = jnp.asarray(chain_size, jnp.float32)[..., None]
    di = jnp.asarray(data_size, jnp.float32)[..., None]
    c = dol.shape[-1]
    return ((chain + di) / c - chain * dol) / jnp.maximum(di, 1e-9)


def min_feasible_data_size(dol: Array, chain_size: Array) -> Array:
    """Corollary 1 / Eq. (A.16): smallest ``D_i`` for which the optimal DSI
    stays on the simplex: ``max_c { C·D_{k-1}·ψ[c] − D_{k-1} }``."""
    dol = jnp.asarray(dol, jnp.float32)
    chain = jnp.asarray(chain_size, jnp.float32)
    c = dol.shape[-1]
    return jnp.maximum(jnp.max(c * chain[..., None] * dol - chain[..., None],
                               axis=-1), 0.0)


def closed_form_iid_distance(variation: Array, chain_size: Array) -> Array:
    """Lemma 2 / Eq. (30): ``W1(ψ_k, U) = ‖φ_k − φ̄_k‖ / D_{P_k}``.

    ``variation`` is the per-class data-size gap φ between the real and the
    optimal next trainer.  Used by the Fig.-2 analytical-results benchmark.
    """
    phi = jnp.asarray(variation, jnp.float32)
    centred = phi - jnp.mean(phi, axis=-1, keepdims=True)
    return jnp.linalg.norm(centred, axis=-1) / jnp.maximum(
        jnp.asarray(chain_size, jnp.float32), 1e-9)


def entropy(dol: Array) -> Array:
    """Shannon entropy of a DoL (Eq. 27) — the quantity Lemma 1 maximizes."""
    eps = 1e-12
    p = jnp.clip(jnp.asarray(dol, jnp.float32), eps, 1.0)
    return -jnp.sum(p * jnp.log(p), axis=-1)


class PlannerState(NamedTuple):
    """Functional (immutable) twin of :class:`DiffusionState`.

    A plain array pytree — every field is a fixed-shape ``jax.Array`` — so a
    whole diffusion round loop over it can live inside ``jax.lax.scan`` /
    ``lax.while_loop`` and be ``vmap``-ed over a leading batch axis (sweep
    cells, topology seeds).  All updates return a *new* state; masked-update
    helpers keep shapes static for the jitted planner
    (:mod:`repro.core.planner`).
    """
    dol: Array            # (..., M, C)
    chain_size: Array     # (..., M)
    visited: Array        # (..., M, N) bool
    holder: Array         # (..., M) int32
    #: Optional wireless-world carry (``repro.channels.world.WorldState``):
    #: the mobile scenario steps it once per diffusion round inside the
    #: jitted planner loop.  ``None`` (an empty pytree subtree) everywhere
    #: else, keeping the pre-world tree structure and traces untouched.
    world: object | None = None

    @classmethod
    def init(cls, num_models: int, num_clients: int, num_classes: int
             ) -> "PlannerState":
        return cls(
            dol=jnp.zeros((num_models, num_classes), jnp.float32),
            chain_size=jnp.zeros((num_models,), jnp.float32),
            visited=jnp.zeros((num_models, num_clients), bool),
            holder=(jnp.arange(num_models, dtype=jnp.int32)
                    % max(num_clients, 1)),
        )

    def record_training(self, model: Array | int, client: Array | int,
                        dsi: Array, data_size: Array | float
                        ) -> "PlannerState":
        """Eq. (2) fold of one (model, client) pair — functional analogue of
        ``DiffusionState.record_training``; jit/scan safe."""
        new_dol, new_size = update_dol(self.dol[model], self.chain_size[model],
                                       jnp.asarray(dsi), data_size)
        return PlannerState(
            dol=self.dol.at[model].set(new_dol),
            chain_size=self.chain_size.at[model].set(new_size),
            visited=self.visited.at[model, client].set(True),
            holder=self.holder.at[model].set(
                jnp.asarray(client, self.holder.dtype)),
            world=self.world,
        )

    def record_round(self, dst: Array, mask: Array, dsi: Array,
                     data_sizes: Array) -> "PlannerState":
        """Fold one diffusion round of hops in a single masked update.

        Args:
          dst:  (M,) int — destination client per model (ignored where
            ``mask`` is False; must still be a valid index).
          mask: (M,) bool — which models actually hop this round.
          dsi / data_sizes: (N, C) / (N,) client control-plane inputs.

        Each scheduled model trains on its destination (constraint 18d makes
        destinations unique, so rows never collide).  Shapes are static —
        this is the update the jitted round loop applies every ``lax.scan`` /
        ``while_loop`` step.
        """
        dst = jnp.asarray(dst, self.holder.dtype)
        new_dol, new_size = update_dol(self.dol, self.chain_size,
                                       dsi[dst], data_sizes[dst])
        m = jnp.arange(self.dol.shape[0])
        return PlannerState(
            dol=jnp.where(mask[:, None], new_dol, self.dol),
            chain_size=jnp.where(mask, new_size, self.chain_size),
            visited=self.visited.at[m, dst].set(self.visited[m, dst] | mask),
            holder=jnp.where(mask, dst, self.holder),
            world=self.world,
        )

    def iid_distances(self, metric: str = "w1_norm") -> Array:
        return iid_distance(self.dol, metric)


@dataclasses.dataclass
class DiffusionState:
    """Host-side bookkeeping for one communication round of FedDif.

    Tracks, per model m: the DoL, the chain data size, and the set of clients
    already visited (constraint 18c — no retraining).  Every field is a NumPy
    array and :meth:`record_training` folds Eq. (2) in NumPy float32, bit for
    bit :func:`update_dol` on the CPU, without touching the device; the jitted
    planner takes the state to the device through :meth:`functional`.
    """
    dol: np.ndarray            # (M, C)
    chain_size: np.ndarray     # (M,)
    visited: np.ndarray        # (M, N) bool — True if client i already trained m
    holder: np.ndarray         # (M,) int — client currently holding model m
    round_index: int = 0

    @classmethod
    def init(cls, num_models: int, num_clients: int, num_classes: int,
             initial_holder: Sequence[int] | None = None) -> "DiffusionState":
        holder = (np.arange(num_models) % num_clients
                  if initial_holder is None else np.asarray(initial_holder))
        return cls(
            dol=np.zeros((num_models, num_classes), np.float32),
            chain_size=np.zeros((num_models,), np.float32),
            visited=np.zeros((num_models, num_clients), bool),
            holder=holder.astype(np.int64),
        )

    def record_training(self, model: int, client: int, dsi: np.ndarray,
                        data_size: float) -> None:
        new_dol, new_size = _fold_dol(np, self.dol[model],
                                      self.chain_size[model],
                                      np.asarray(dsi, np.float32), data_size)
        self.dol[model] = new_dol
        self.chain_size[model] = new_size
        self.visited[model, client] = True
        self.holder[model] = client

    def iid_distances(self, metric: str = "w1_norm") -> np.ndarray:
        return np.asarray(iid_distance(jnp.asarray(self.dol), metric))

    def snapshot(self) -> "DiffusionState":
        """Deep copy — used by the plan cache to store post-plan state."""
        return DiffusionState(dol=self.dol.copy(),
                              chain_size=self.chain_size.copy(),
                              visited=self.visited.copy(),
                              holder=self.holder.copy(),
                              round_index=self.round_index)

    def restore(self, other: "DiffusionState") -> None:
        """Overwrite this state in place from a snapshot (cache replay)."""
        self.dol = other.dol.copy()
        self.chain_size = other.chain_size.copy()
        self.visited = other.visited.copy()
        self.holder = other.holder.copy()
        self.round_index = other.round_index

    def functional(self) -> PlannerState:
        """Device-ready immutable view for the jitted planner plane."""
        return PlannerState(
            dol=jnp.asarray(self.dol, jnp.float32),
            chain_size=jnp.asarray(self.chain_size, jnp.float32),
            visited=jnp.asarray(self.visited, bool),
            holder=jnp.asarray(self.holder, jnp.int32),
        )

    def update_from(self, fstate: PlannerState, rounds_advanced: int = 0
                    ) -> None:
        """Adopt a post-plan :class:`PlannerState` (in-place, host arrays)."""
        self.dol = np.asarray(fstate.dol, np.float32)
        self.chain_size = np.asarray(fstate.chain_size, np.float32)
        self.visited = np.asarray(fstate.visited, bool)
        self.holder = np.asarray(fstate.holder, np.int64)
        self.round_index += int(rounds_advanced)
