"""Mixture-of-Experts layers: a router, then a dispatch, chosen apart.

Routers (``MoESpec.scoring``):

* ``"softmax"`` (Mixtral, Qwen3-MoE): top-k of a softmax, the weights
  renormalised over the k, and a Switch-style load-balance auxiliary loss.
* ``"sigmoid"`` (DeepSeek-V3 / Moonlight, ``noaux_tc`` with one group):
  per-expert sigmoid scores in float32, the top k of score plus a
  selection-only bias, weights the unbiased scores of the chosen experts
  normalised over the k and times ``routed_scale``.  Its sequence-wise
  auxiliary loss is left out (aux is 0): the configurations that use it
  keep the router frozen.

Dispatches (``MoESpec.grouped``):

* Buffers (the default): tokens are *gathered* into per-expert buffers of
  static capacity (TPU-native adaptation — see DESIGN.md §2) using indices
  derived from an argsort of the routing assignment, experts run as one
  batched einsum over the expert axis (shardable over the ``model`` mesh
  axis → the gather/scatter lower to the MoE all-to-all under SPMD), and
  results scatter-add back weighted by the router.  With capacity
  ``C = ceil(T·k/E · capacity_factor)`` overflowing tokens are dropped
  (Switch/GShard semantics) and the cost is the active-parameter FLOPs;
  ``dropless`` sizes the buffers to the worst case (T rows an expert).
* Grouped: dropless at the cost of the routed rows.  The routed pairs are
  sorted by expert, each expert's rows padded to a tile, and the grouped
  product ``kernels.ops.moe_gmm`` runs over the experts the layer holds.
  The layer may hold the first ``experts_held`` of the router's
  ``num_experts`` experts — one chip's share under expert parallelism —
  and then routes over all of them and computes the part of the result its
  own experts give; no code stands in for the absent experts or their
  exchange.  Under ``vmap`` with the expert weights unbatched (the fleet's
  client slots over one frozen base) the batch axis is folded into the
  token axis, so one dispatch serves every slot; while the weights are
  frozen the backward pass runs only the input-gradient products, and
  where they train it also gives each expert's weight gradient.

Either way the shared experts, if any, run on every token, and the layer
also returns how many routed (token, expert) pairs its experts computed.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.custom_derivatives import SymbolicZero

from repro.kernels import ops as kernel_ops
from repro.kernels.moe_gmm import GMM_ROWS
from repro.models import layers as L

Array = jax.Array
Params = Any

__all__ = ["MoESpec", "init_moe", "moe_forward", "route_softmax",
           "route_sigmoid", "held_experts"]


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    num_shared_experts: int = 0
    compute_dtype: Any = jnp.bfloat16
    # Dropless dispatch: buffers sized to the worst case (every token on one
    # expert) so no token is ever dropped.  This is what Mixtral / Qwen3-MoE
    # reference implementations do, and it is REQUIRED for prefill ≡ decode:
    # capacity drops depend on which other tokens share the batch, so a
    # token kept at decode (T=1 step, no competition) can be dropped at
    # prefill — tests/test_models_consistency.py pins the parity.
    dropless: bool = False
    # The router (module docstring) and the factor on its weights.
    scoring: str = "softmax"
    routed_scale: float = 1.0
    # The grouped dispatch, and the experts this layer holds (0: all; a
    # share needs the grouped dispatch).
    grouped: bool = False
    experts_held: int = 0

    def __post_init__(self):
        if self.experts_held and not self.grouped:
            raise ValueError("a layer holding a share of the experts needs "
                             "the grouped dispatch")

    def capacity(self, num_tokens: int) -> int:
        if self.dropless:
            # Each token routes to ≤1 slot per expert (top-k indices are
            # distinct), so cap = T can never overflow.  Static worst-case
            # buffers are the price of dropless under fixed shapes: E·T
            # dispatch rows vs T·k·cf capacity-bounded — E/(k·cf)× more
            # expert-FFN work (12.8× for qwen3_moe's E=128/k=8), mostly
            # multiplying zeros.  The grouped dispatch costs exactly the
            # routed rows.
            c = num_tokens
        else:
            c = int(num_tokens * self.top_k * self.capacity_factor
                    / self.num_experts)
        return max(8, -(-c // 8) * 8)    # round up to 8 for TPU lanes


def init_moe(key, spec: MoESpec) -> Params:
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    d, f = spec.d_model, spec.d_ff_expert
    e = spec.experts_held or spec.num_experts
    scale = 1.0 / (d ** 0.5)
    p = {
        "router": L.init_dense(kr, d, spec.num_experts, scale=0.02),
        "w_gate": jax.random.normal(k1, (e, d, f), jnp.float32) * scale,
        "w_up": jax.random.normal(k2, (e, d, f), jnp.float32) * scale,
        "w_down": jax.random.normal(k3, (e, f, d), jnp.float32)
                  * (1.0 / (f ** 0.5)),
    }
    if spec.scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((spec.num_experts,), jnp.float32)
    if spec.num_shared_experts:
        p["shared"] = L.init_swiglu(ks, d,
                                    f * spec.num_shared_experts)
    return p


def moe_forward(p: Params, spec: MoESpec,
                x: Array) -> tuple[Array, Array, Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar, routed pairs the
    layer's experts computed, int32)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if spec.scoring == "sigmoid":
        top_e, top_w = route_sigmoid(p, spec, xt)
        aux = jnp.zeros((), jnp.float32)
    else:
        top_e, top_w, aux = route_softmax(p, spec, xt)
    dispatch = _grouped if spec.grouped else _buffers
    out, rows = dispatch(p, spec, xt, top_e, top_w)
    if spec.num_shared_experts:
        out = out + L.swiglu(p["shared"], xt, spec.compute_dtype
                             ).astype(jnp.float32)
    return out.reshape(b, s, d).astype(x.dtype), aux, rows


def route_softmax(p: Params, spec: MoESpec,
                  xt: Array) -> tuple[Array, Array, Array]:
    """(T, D) -> top experts (T, k), their softmax weights renormalised over
    the k (T, k) float32, and the load-balance loss (Switch Transformer
    eq. 4)."""
    e, k = spec.num_experts, spec.top_k
    logits = L.dense(p["router"], xt, jnp.float32)            # (T, E) fp32
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                    # (T, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1), axis=0)
    aux = spec.router_aux_coef * e * jnp.sum(me * ce)
    return top_e, top_p, aux


def route_sigmoid(p: Params, spec: MoESpec, xt: Array) -> tuple[Array, Array]:
    """(T, D) -> top experts (T, k) and their weights (T, k) float32: the
    top k of sigmoid score plus ``router_bias``, weighted by the unbiased
    scores normalised over the k, times ``routed_scale``."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(scores + p["router_bias"], spec.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_e, top_w * spec.routed_scale


def _buffers(p: Params, spec: MoESpec, xt: Array, top_e: Array,
             top_w: Array) -> tuple[Array, Array]:
    """Per-expert buffers of static capacity: (T, D) float32 and the pairs
    kept."""
    t, d = xt.shape
    cd = spec.compute_dtype
    e, k = spec.num_experts, spec.top_k
    cap = spec.capacity(t)

    # ---- capacity assignment via sort by expert id ----
    flat_e = top_e.reshape(t * k)                             # (T·k,)
    flat_p = top_w.reshape(t * k)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    sorted_p = flat_p[order]
    # position of each routed pair within its expert's buffer:
    # arange minus the start offset of the pair's expert segment.
    counts = jnp.bincount(sorted_e, length=e)                 # (E,)
    starts = jnp.cumsum(counts) - counts                      # exclusive scan
    pos_in_expert = jnp.arange(t * k) - starts[sorted_e]
    keep = pos_in_expert < cap
    # buffer slot = expert*cap + pos; dropped pairs park in a trash slot.
    slot = jnp.where(keep, sorted_e * cap + pos_in_expert, e * cap)

    # ---- gather tokens into (E·cap, D) buffers ----
    buf_tok = jnp.zeros((e * cap + 1,), jnp.int32).at[slot].set(
        sorted_tok, mode="drop")
    buf_valid = jnp.zeros((e * cap + 1,), jnp.bool_).at[slot].set(
        keep, mode="drop")
    gathered = jnp.take(xt, buf_tok[:e * cap], axis=0)        # (E·cap, D)
    gathered = jnp.where(buf_valid[:e * cap, None], gathered, 0.0)
    ex_in = gathered.reshape(e, cap, d).astype(cd)

    # ---- batched expert FFN (SwiGLU) ----
    wg = p["w_gate"].astype(cd)
    wu = p["w_up"].astype(cd)
    wd = p["w_down"].astype(cd)
    h = L.silu(jnp.einsum("ecd,edf->ecf", ex_in, wg)) \
        * jnp.einsum("ecd,edf->ecf", ex_in, wu)
    ex_out = jnp.einsum("ecf,efd->ecd", h, wd)                # (E, cap, D)

    # ---- combine: scatter-add back weighted by router prob ----
    flat_out = ex_out.reshape(e * cap, d)
    pair_out = jnp.take(flat_out, jnp.minimum(slot, e * cap - 1), axis=0)
    pair_out = jnp.where(keep[:, None], pair_out, 0.0)
    contrib = pair_out.astype(jnp.float32) * sorted_p[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[sorted_tok].add(contrib)
    return out, jnp.sum(keep, dtype=jnp.int32)


def _grouped(p: Params, spec: MoESpec, xt: Array, top_e: Array,
             top_w: Array) -> tuple[Array, Array]:
    """Sorted rows through ``moe_gmm`` over the experts the layer holds
    (experts ``[0, held)`` of the router; a pair routed elsewhere
    contributes nothing here): (T, D) float32 and the pairs computed."""
    cd = spec.compute_dtype
    held = p["w_gate"].shape[0]
    w_gu = jnp.concatenate([p["w_gate"], p["w_up"]], axis=-1).astype(cd)
    out = held_experts(xt.astype(cd), top_e, top_w, w_gu,
                       p["w_down"].astype(cd))
    return out, jnp.sum(top_e < held, dtype=jnp.int32)


def _dispatch(top_e: Array, held: int) -> tuple:
    """Rows of the held experts' pairs, sorted by expert, each expert's
    rows padded to a multiple of ``GMM_ROWS``.  Returns ``(pos, row_pair,
    valid, sizes)``: the row of each (token, choice) pair (T·k,), the pair
    of each row (R,), whether a row holds a pair, and the padded rows of
    each held expert.  R is static: every token's k choices are distinct,
    so at most T·min(k, held) pairs reach the held experts."""
    t, k = top_e.shape
    tm = GMM_ROWS
    rows = -(-t * min(k, held) // tm) * tm + held * tm
    flat = top_e.reshape(t * k)
    local = jnp.where(flat < held, flat, held)      # held `held`: elsewhere
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    counts = jnp.bincount(local, length=held + 1)[:held]
    sizes = (counts + tm - 1) // tm * tm
    first = jnp.minimum(sorted_e, held - 1)
    rank = jnp.arange(t * k) - (jnp.cumsum(counts) - counts)[first]
    slot = jnp.where(sorted_e < held,
                     (jnp.cumsum(sizes) - sizes)[first] + rank, rows)
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(slot)
    row_pair = jnp.zeros((rows + 1,), jnp.int32).at[slot].set(order)[:rows]
    valid = jnp.zeros((rows + 1,), bool).at[slot].set(True)[:rows]
    return pos, row_pair, valid, sizes.astype(jnp.int32)


def _experts_fwd(x, top_e, top_w, w_gu, w_d):
    """Token-flat forward: x (T, D), top_e/top_w (T, k) -> (T, D) f32."""
    t, d = x.shape
    held, k = w_gu.shape[0], top_e.shape[1]
    if t == 0:
        return jnp.zeros((0, d), jnp.float32)
    pos, row_pair, valid, sizes = _dispatch(top_e, held)
    xr = jnp.where(valid[:, None], x[row_pair // k], 0)
    gu = kernel_ops.moe_gmm(xr, w_gu, sizes)
    g, u = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
    h = (L.silu(g) * u).astype(x.dtype)
    eo = kernel_ops.moe_gmm(h, w_d, sizes)
    mine = (top_e.reshape(-1) < held)[:, None]
    pair = jnp.where(mine, eo[jnp.minimum(pos, eo.shape[0] - 1)], 0)
    return jnp.einsum("tkd,tk->td", pair.reshape(t, k, d).astype(jnp.float32),
                      top_w)


def _experts_bwd(x, top_e, top_w, w_gu, w_d, dy):
    """Token-flat input gradient: (dx (T, D), d top_w (T, k)), for frozen
    expert weights: only the products with transposed weights run."""
    t, d = x.shape
    held, k = w_gu.shape[0], top_e.shape[1]
    if t == 0:
        return jnp.zeros_like(x), jnp.zeros_like(top_w)
    pos, row_pair, valid, sizes = _dispatch(top_e, held)
    tok = row_pair // k
    xr = jnp.where(valid[:, None], x[tok], 0)
    gu = kernel_ops.moe_gmm(xr, w_gu, sizes)
    g, u = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
    sg = jax.nn.sigmoid(g)
    h = g * sg * u
    dyr = jnp.where(valid[:, None], dy[tok], 0).astype(x.dtype)
    dh1 = kernel_ops.moe_gmm(dyr, w_d, sizes,
                             transpose_w=True).astype(jnp.float32)
    # d out / d w[pair] = eo[row] . dy[tok] = h[row] . (dy[tok] @ w_dᵀ)
    dw_row = jnp.sum(dh1 * h.astype(x.dtype).astype(jnp.float32), axis=-1)
    mine = top_e.reshape(-1) < held
    dtop_w = jnp.where(mine, dw_row[jnp.minimum(pos, dw_row.shape[0] - 1)],
                       0).reshape(t, k)
    dh = dh1 * top_w.reshape(-1)[row_pair][:, None]
    dg = dh * u * sg * (1 + g * (1 - sg))
    du = dh * g * sg
    dxr = kernel_ops.moe_gmm(jnp.concatenate([dg, du], -1).astype(x.dtype),
                             w_gu, sizes, transpose_w=True)
    dxr = jnp.where(valid[:, None], dxr.astype(jnp.float32), 0)
    dx = jnp.zeros((t, d), jnp.float32).at[tok].add(dxr)
    return dx.astype(x.dtype), dtop_w


def _fold_slots(fn, tokens: int, fold: bool = True):
    """``fn`` with a batching rule: where ``fold`` and the arguments after
    the first ``tokens`` (the expert weights) are unbatched, the batch axis
    of the first ``tokens`` arguments is folded into their token axis and
    ``fn`` runs once; otherwise ``fn`` runs once per batch element
    (``lax.map``: the ragged products have no general batching rule)."""
    folded = custom_vmap(fn)

    @folded.def_vmap
    def rule(axis_size, in_batched, *args):
        if not fold or any(in_batched[tokens:]):
            def one(batched):
                it = iter(batched)
                return folded(*[next(it) if b else a
                                for a, b in zip(args, in_batched)])
            out = jax.lax.map(one, [a for a, b in zip(args, in_batched)
                                    if b])
            return out, jax.tree.map(lambda _: True, out)
        toks = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args[:tokens], in_batched[:tokens])]
        flat = [a.reshape((-1,) + a.shape[2:]) for a in toks]
        out = folded(*flat, *args[tokens:])
        out = jax.tree.map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree.map(lambda _: True, out)
    return folded


def _experts_vjp(x, top_e, top_w, dy, w_gu, w_d):
    """The unfolded forward's own VJP, for expert weights in training: its
    grouped products also give each expert's weight gradient
    (``kernels.ops.moe_gmm``).  Returns (dx, d top_w, d w_gu, d w_d)."""
    _, vjp = jax.vjp(lambda x, tw, gu, d: _experts_fwd(x, top_e, tw, gu, d),
                     x, top_w, w_gu, w_d)
    return vjp(dy)


_fwd_folded = _fold_slots(_experts_fwd, tokens=3)
_bwd_folded = _fold_slots(
    lambda x, top_e, top_w, dy, w_gu, w_d: _experts_bwd(
        x, top_e, top_w, w_gu, w_d, dy), tokens=4)
# Per-slot weight gradients: never folded.
_vjp_mapped = _fold_slots(_experts_vjp, tokens=4, fold=False)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _Trains:
    """Whether the expert weights train: static, beside the residuals."""
    weights: bool


@jax.custom_vjp
def held_experts(x, top_e, top_w, w_gu, w_d):
    """The held experts' part of the layer: x (T, D) in the compute dtype,
    top_e (T, k) router indices, top_w (T, k) weights, w_gu (held, D, 2F)
    gate and up, w_d (held, F, D).  Returns (T, D) float32."""
    return _fwd_folded(x, top_e, top_w, w_gu, w_d)


def _held_fwd(*primals):
    args = tuple(a.value for a in primals)
    trains = _Trains(primals[3].perturbed or primals[4].perturbed)
    return _fwd_folded(*args), (args, trains)


def _held_bwd(res, dy):
    (x, top_e, top_w, w_gu, w_d), trains = res
    if isinstance(dy, SymbolicZero):
        dy = jnp.zeros(dy.shape, dy.dtype)
    if trains.weights:
        dx, dtop_w, dgu, dd = _vjp_mapped(x, top_e, top_w, dy, w_gu, w_d)
        return dx, None, dtop_w, dgu, dd
    dx, dtop_w = _bwd_folded(x, top_e, top_w, dy, w_gu, w_d)
    return dx, None, dtop_w, None, None


held_experts.defvjp(_held_fwd, _held_bwd, symbolic_zeros=True)
