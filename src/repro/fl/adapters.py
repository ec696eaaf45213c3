"""The adapter hop plane: frozen-base / trainable-adapter views + int8 wire.

FedDif's hop payload does not have to be the model.  A :class:`AdapterView`
splits a task's parameters into a frozen base (broadcast once, charged on
the round-0 downlink) and a trainable adapter pytree (LoRA factors for the
"lm" task) that is the *only* state the executors train, diffuse, mix
(Eq. 10/11) and aggregate.  Tasks without a split (``TaskModel.split is
None`` — every CNN/MLP sweep) degenerate to the identity view: the exact
``model.init``/``model.loss`` objects pass through unwrapped, so full-params
runs are bit-identical to the pre-adapter code path.

A zoo model (``repro.models.zoo``) gets its adapters from :func:`lora_view`:
rank-r LoRA factors on named projections, over a frozen base the caller
hands in.  Its loss is a :class:`FrozenLoss`, which carries the base
beside the function: the fleet step takes the base as an argument shared
by every client slot, never as a constant of its compiled program.

On the wire, a hop payload is additionally packed to int8 when
``FLConfig.hop_quant == "int8"``: the flattened adapter is cut into
QUANT_BLOCK-element row-blocks and each block moves as int8 codes plus one
fp32 absmax scale (``kernels/quant.py``).  :func:`packed_bits` is the
Eq.-15 payload size S of that format — 8·block + 32 bits per row-block —
charged per D2D hop by the schedulers via ``spec_adapter_bits``.

Every executor applies exactly one pack→unpack roundtrip per PermuteOp to
every slot (the roundtrip is what the receiving device would decode), so
host / fleet / sharded runs stay numerically identical: per-row packing
commutes with the row gathers/ring shifts that implement the move.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import model_bits
from repro.kernels import ops as kernel_ops
from repro.kernels.quant import QUANT_BLOCK

Params = Any

__all__ = ["AdapterView", "make_adapter_view", "FrozenLoss", "lora_init",
           "lora_merge", "lora_view", "packed_bits", "pack_rows",
           "unpack_rows", "quant_roundtrip_rows", "quant_roundtrip_tree",
           "quant_roundtrip_slot", "QUANT_BLOCK"]


def pack_rows(flat: jax.Array, *, block: int = QUANT_BLOCK,
              implementation: str = "auto"):
    """(C, F) fp32 client-stacked flat params → ((C, Fp) int8 codes,
    (C, Fp/block) fp32 scales), Fp = F padded up to a block multiple.
    Per client row the layout matches :func:`quant_roundtrip_slot`, so a
    packed row is the same wire bytes no matter which executor sends it."""
    c, f = flat.shape
    fp = -(-f // block) * block
    if fp != f:
        flat = jnp.pad(flat, ((0, 0), (0, fp - f)))
    r = fp // block
    q, s = kernel_ops.quant_pack(
        flat.astype(jnp.float32).reshape(c * r, block),
        implementation=implementation)
    return q.reshape(c, fp), s.reshape(c, r)


def unpack_rows(q: jax.Array, scales: jax.Array, f: int, *,
                implementation: str = "auto") -> jax.Array:
    """Inverse of :func:`pack_rows`; ``f`` is the unpadded feature count."""
    c, fp = q.shape
    r = scales.shape[1]
    x = kernel_ops.quant_unpack(q.reshape(c * r, fp // r),
                                scales.reshape(c * r),
                                implementation=implementation)
    return x.reshape(c, fp)[:, :f]


def quant_roundtrip_rows(flat: jax.Array, *, block: int = QUANT_BLOCK,
                         implementation: str = "auto") -> jax.Array:
    """pack→unpack of a (C, F) block: what the hop destination decodes."""
    q, s = pack_rows(flat, block=block, implementation=implementation)
    return unpack_rows(q, s, flat.shape[1], implementation=implementation)


def quant_roundtrip_tree(params: Params, *,
                         implementation: str = "auto") -> Params:
    """Roundtrip a client-stacked pytree per client row (FleetExecutor)."""
    from repro.kernels.diffusion import stack_ravel, stack_unravel
    flat, spec = stack_ravel(params)
    return stack_unravel(quant_roundtrip_rows(flat,
                                              implementation=implementation),
                         spec)


def quant_roundtrip_slot(params: Params, *,
                         implementation: str = "auto") -> Params:
    """Roundtrip one unstacked slot tree (HostExecutor).  Flattens in
    ``stack_ravel``'s leaf-concat order so the row-block boundaries — and
    therefore the decoded values — coincide with the stacked executors'."""
    leaves, treedef = jax.tree.flatten(params)
    flat = jnp.concatenate([x.reshape(1, -1).astype(jnp.float32)
                            for x in leaves], axis=1)
    out = quant_roundtrip_rows(flat, implementation=implementation)[0]
    new, off = [], 0
    for x in leaves:
        n = int(np.prod(x.shape))
        new.append(out[off:off + n].reshape(x.shape).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, new)


def packed_bits(template: Params, *, block: int = QUANT_BLOCK) -> float:
    """S for one int8-packed hop (Eq. 15 numerator): 8 bits per padded
    element plus one fp32 scale per row-block.  ``template`` may hold
    arrays or ShapeDtypeStructs."""
    f = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(template))
    rows = -(-f // block)
    return float(rows * (8 * block + 32))


@dataclasses.dataclass(frozen=True)
class AdapterView:
    """What ``run_federated`` sees of a task: init/loss over the *hop
    payload* tree, a merge back to full params for eval, and the one-time
    base broadcast charge (0.0 when the view is the identity)."""
    init_fn: Callable[[jax.Array], Params]
    loss_fn: Callable[[Params, dict], jax.Array]
    merge_fn: Callable[[Params], Params]
    base_bits: float
    base: Params | None


def make_adapter_view(model, fl_cfg, adapter_hops: bool = True) -> AdapterView:
    """Build the view ``run_federated`` trains/hops over.

    Full-params tasks (``model.split is None``) or ``adapter_hops=False``
    return the identity view with the *unwrapped* ``model.init`` /
    ``model.loss`` — bit-identical traces to the pre-adapter code path.
    Otherwise the base is fixed from the run seed (every client would
    derive the same base from the round-0 broadcast), the hop payload is
    ``split(init)[1]``, and the loss closes over the frozen base."""
    if not adapter_hops or model.split is None:
        return AdapterView(model.init, model.loss, lambda p: p, 0.0, None)
    base, _ = model.split(model.init(jax.random.PRNGKey(fl_cfg.seed)))

    def init_fn(key):
        return model.split(model.init(key))[1]

    def loss_fn(adapter, batch):
        return model.loss(model.merge(base, adapter), batch)

    def merge_fn(adapter):
        return model.merge(base, adapter)

    return AdapterView(init_fn, loss_fn, merge_fn,
                       model_bits(base, fl_cfg.bits_per_param), base)


@dataclasses.dataclass(frozen=True, eq=False)
class FrozenLoss:
    """A loss over the trained tree that reads a frozen tree:
    ``fn(params, batch, frozen) -> (loss, counts)``, where ``counts`` maps
    ``repro.obs`` counter names to int32 device scalars the step computes
    (the MoE layers' routed rows, ``fl.exec.moe_rows``).  Called as
    ``loss(params, batch)`` it closes over ``frozen`` and gives the loss;
    ``FleetExecutor`` instead passes ``frozen`` to its jitted step as an
    unbatched argument, and adds the counts up on the device."""
    fn: Callable[[Params, dict, Params], tuple]
    frozen: Params

    def __call__(self, params: Params, batch: dict) -> jax.Array:
        return self.fn(params, batch, self.frozen)[0]


def _lora_paths(tree, targets: tuple, path=()):
    """Paths of the ``{"w": ...}`` projections named in ``targets``."""
    if isinstance(tree, dict):
        if path and path[-1] in targets and "w" in tree:
            return [path]
        items = tree.items()
    elif isinstance(tree, (list, tuple)):
        items = enumerate(tree)
    else:
        return []
    return [p for k, v in items for p in _lora_paths(v, targets, path + (k,))]


def _nest(paths_leaves: list, like):
    """The nested dicts/lists of ``like`` that lead to ``paths_leaves``."""
    if isinstance(like, dict):
        out = {}
        for k, v in like.items():
            sub = [(p[1:], x) for p, x in paths_leaves if p[0] == k]
            if sub:
                out[k] = sub[0][1] if sub[0][0] == () else _nest(sub, v)
        return out
    return [_nest([(p[1:], x) for p, x in paths_leaves if p[0] == i], v)
            for i, v in enumerate(like)]


def lora_init(base_shapes, key, rank: int, targets: tuple) -> Params:
    """Rank-``rank`` factors for each projection named in ``targets`` (a
    ``{"w": (..., d_in, d_out)}``, leading axes stacked layers): ``lora_a``
    N(0, 1/d_in), ``lora_b`` zero, so the adapted model starts as the
    base.  The tree holds the paths to those projections alone."""
    paths = _lora_paths(base_shapes, tuple(targets))
    out = []
    for i, path in enumerate(paths):
        w = base_shapes
        for k in path:
            w = w[k]
        *lead, d_in, d_out = w["w"].shape
        a = jax.random.normal(jax.random.fold_in(key, i),
                              (*lead, d_in, rank), jnp.float32)
        out.append((path, {"lora_a": a / np.sqrt(d_in),
                           "lora_b": jnp.zeros((*lead, rank, d_out),
                                               jnp.float32)}))
    return _nest(out, base_shapes)


def lora_merge(base: Params, adapter: Params) -> Params:
    """The base with each adapted projection's factors beside its ``w``
    (``models.layers.dense`` applies them)."""
    if isinstance(adapter, dict):
        return {k: (lora_merge(v, adapter[k]) if k in adapter else v)
                for k, v in base.items()} | \
            {k: v for k, v in adapter.items() if k not in base}
    if isinstance(adapter, list):
        return [lora_merge(b, a) for b, a in zip(base, adapter)]
    return adapter


def lora_view(model, base: Params, rank: int, targets: tuple) -> AdapterView:
    """LoRA adapters over a zoo model's frozen ``base``.  Batches are FL
    token rows ``{"x": (B, S + 1)}``: tokens ``x[:, :-1]``, labels
    ``x[:, 1:]``."""
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          base)

    moe = model.cfg.moe is not None

    def loss(adapter, batch, frozen):
        tok = batch["x"].astype(jnp.int32)
        out = model.loss(lora_merge(frozen, adapter),
                         {"tokens": tok[:, :-1], "labels": tok[:, 1:]},
                         with_rows=moe)
        return (out[0], {"fl.exec.moe_rows": out[1]}) if moe else (out, {})

    return AdapterView(
        init_fn=lambda key: lora_init(shapes, key, rank, targets),
        loss_fn=FrozenLoss(loss, base),
        merge_fn=lambda adapter: lora_merge(base, adapter),
        base_bits=float(sum(8 * a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(shapes))),
        base=base)
