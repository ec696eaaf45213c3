"""Grouped matrix product over the experts a chip holds (``moe_gmm``).

``out[r] = x[r] @ w[g(r)]`` for the rows ``r`` of a row block sorted by
expert: the first ``group_sizes[0]`` rows go to expert 0, the next
``group_sizes[1]`` to expert 1, and so on.  The MoE dispatch
(``repro.models.moe``) pads every group to a multiple of :data:`GMM_ROWS`,
so each row tile belongs to one expert; rows after the last group are
padding, and their output is left unspecified.

The grid walks (column tile, row tile) with the row tile innermost.  A
row tile past the last group repeats the last active tile's block indices,
so it fetches nothing and computes nothing: the kernel's cost is the rows
routed to the held experts (plus the padding of each group to a tile), not
the static worst-case buffer.  The weight block of consecutive tiles of one
expert keeps its index, so each expert's weights are read once per column
tile.  Each block holds the whole contraction axis.

``transpose_w`` multiplies by the transposed weights, ``x @ w[g]ᵀ``: the
input gradient of the forward product (``kernels.ops.moe_gmm`` makes it
the product's VJP).  The weight gradient, each expert's ``x[g]ᵀ · dy[g]``,
is :func:`moe_gmm_wgrad`, XLA's ragged product: only experts in training
need it, and no configuration of the benchmark trains them.

The installed megablox ``gmm``/``tgmm`` (``jax.experimental.pallas.ops.
tpu.megablox``) compute the same products, but their ``pallas_call`` takes
no name, so a device trace could not tell the expert product from other
kernels; and they tile the contraction axis through a float32 scratch
accumulator, where here the whole axis (at most 2F = 2,816) fits one
block beside its rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["GMM_ROWS", "moe_gmm_pallas", "moe_gmm_ref", "moe_gmm_wgrad"]

GMM_ROWS = 128              # rows per tile: the MXU's height
W_BLOCK_BYTES = 6 << 20     # a weight block, double-buffered, beside its rows
VMEM_LIMIT = 64 << 20       # of the v5e's 128 MiB


def _col_tile(n: int, k: int, itemsize: int) -> int:
    """The widest column tile (a multiple of 128 dividing ``n``) whose
    (k, tile) weight block fits :data:`W_BLOCK_BYTES`; ``n`` itself where it
    is no multiple of 128 (a block dim equal to the array's needs none)."""
    if n % 128:
        return n
    best = 128
    for t in range(128, n + 1, 128):
        if n % t == 0 and k * t * itemsize <= W_BLOCK_BYTES:
            best = t
    return best


def _tile_groups(group_sizes: jax.Array, tiles: int, tm: int):
    """(tiles,) expert of each row tile, and the count of tiles with rows."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32)) // tm
    ids = jnp.arange(tiles, dtype=jnp.int32)
    expert = jnp.searchsorted(ends, ids, side="right").astype(jnp.int32)
    return (jnp.minimum(expert, group_sizes.shape[0] - 1),
            ends[-1:].astype(jnp.int32))


def moe_gmm_pallas(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   transpose_w: bool = False,
                   interpret: bool = False) -> jax.Array:
    """x (R, K) with R a multiple of :data:`GMM_ROWS`; w (E, K, N), or
    (E, N, K) with ``transpose_w``; group_sizes (E,) int32, each a multiple
    of :data:`GMM_ROWS`.  Returns (R, N) in x's dtype, accumulated in
    float32."""
    r, k = x.shape
    tm = GMM_ROWS
    if r % tm:
        raise ValueError(f"rows {r} are not a multiple of {tm}")
    n = w.shape[1] if transpose_w else w.shape[2]
    tn = _col_tile(n, k, w.dtype.itemsize)
    tiles = r // tm
    tile_expert, active = _tile_groups(group_sizes, tiles, tm)

    def row(i, na):
        return jnp.maximum(jnp.minimum(i, na[0] - 1), 0)

    if transpose_w:
        w_spec = pl.BlockSpec((None, tn, k),
                              lambda j, i, te, na: (te[row(i, na)], j, 0))
        dims = (((1,), (1,)), ((), ()))
    else:
        w_spec = pl.BlockSpec((None, k, tn),
                              lambda j, i, te, na: (te[row(i, na)], 0, j))
        dims = (((1,), (0,)), ((), ()))

    def kernel(te_ref, na_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < na_ref[0])
        def _():
            o_ref[...] = jax.lax.dot_general(
                x_ref[...], w_ref[...], dims,
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda j, i, te, na: (row(i, na), 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, te, na: (row(i, na), j))),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, active, x, w.astype(x.dtype))


def moe_gmm_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                transpose_w: bool = False) -> jax.Array:
    """The same product through ``jax.lax.ragged_dot`` (rows after the last
    group read 0)."""
    if transpose_w:
        w = jnp.swapaxes(w, 1, 2)
    out = jax.lax.ragged_dot(x, w.astype(x.dtype),
                             group_sizes.astype(jnp.int32),
                             preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def moe_gmm_wgrad(x: jax.Array, dy: jax.Array, group_sizes: jax.Array, *,
                  transpose_w: bool = False) -> jax.Array:
    """The weight gradient of the product: for each expert ``g``,
    ``x[g]ᵀ · dy[g]`` over its rows, (E, K, N) float32; (E, N, K) with
    ``transpose_w``.  Rows after the last group count nothing."""
    if transpose_w:
        x, dy = dy, x
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        x, dy, group_sizes.astype(jnp.int32), dims,
        preferred_element_type=jnp.float32)
