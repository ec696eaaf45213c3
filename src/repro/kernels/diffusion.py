"""Pallas kernels for the FL diffusion data plane (Eq. 10/11 + STC hops).

Three hot loops of the communication round run as tiled single-pass kernels
instead of long per-leaf ``jnp`` chains:

* :func:`mix_aggregate_pallas` — Eq. (10)/(11): one weighted reduction
  ``out[g, f] = Σ_c w[g, c] · x[c, f]`` over a *flattened client-stacked
  parameter block* ``x`` (every pytree leaf raveled and concatenated on one
  feature axis).  A ``MixOp`` is ``w = W`` (the (C, C) mixing matrix), the
  Eq.-11 aggregation is ``w = weights[None, :]`` (one output row), and a
  sharded partial is ``w = Wᵀ_local`` — all the per-leaf
  ``einsum → mask → psum`` chains in ``repro.fl.executors`` become ONE
  MXU pass per feature tile, one HBM read of the fleet.

* :func:`stc_rows_pallas` — per-row (per-client) sparse ternary compression
  fused with the masked blend of ``fedshard.masked_stc_compress``:
  ``out[c] = mask[c] ? ref + μ_c·sign(x_c − ref)·1[|x_c − ref| ≥ τ_c]
  : x[c]``.  Two tiled passes (row-wise survivor reduction, then
  ternarize+blend) replace the host composite (a ``vmap`` of ``top_k`` +
  scatter per client per leaf).  τ itself stays an XLA sort, exactly like
  ``kernels.stc_compress`` (DESIGN.md §2).

* :func:`dol_bid_scores_pallas` — the planner's candidate IID-distance
  matrix (Sec. III-B / Eq. 32 bids) without materializing the (M, N, C)
  candidate-DoL tensor.  Centering DoLs/DSIs on the uniform point
  ``u = 1/C`` collapses Eq. (2) + Eq. (B.1) to a rank-C matmul plus
  rank-1 corrections::

      cand − u·1 = (a·ψc + b·dc)/s′ + u·δ·1,
          ψc = ψ − u,  dc = d − u,  s′ = max(a + b, 1),  δ = (a+b)/s′ − 1
      ‖cand − u‖² = (a²‖ψc‖² + 2ab·(ψc·dc) + b²‖dc‖²)/s′²
                    + 2uδ·(a·Σψc + b·Σdc)/s′ + C·u²·δ²

  ``ψc·dcᵀ`` is an (M, C)×(C, N) MXU contraction; everything else is a
  row or column statistic.  The centered form is exact *and* cancellation
  free as the DoLs converge to uniform (dist → 0), where the naive
  ``‖cand‖² − 1/C`` expansion loses all precision.
  :func:`dol_bid_scores_xla_fused` is the same math as a pure-jnp twin —
  the fast XLA path for large-N pre-planning and the oracle the kernel is
  tested against (which is itself validated against
  ``repro.core.dol.iid_distance_candidates``).

All kernels compile for the TPU by default and take ``interpret=True``
only when asked, so CI's pallas-interpret job runs the bodies on CPU;
dispatch (auto/pallas/pallas_interpret/ref) lives in ``kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mix_aggregate_pallas", "stc_rows_pallas", "dol_bid_scores_pallas",
           "dol_bid_scores_xla_fused", "stack_ravel", "stack_unravel"]

BLOCK_F = 8192      # feature-axis tile (fp32 (C, BF) block in VMEM)
BLOCK_C = 1024      # client-axis tile (streaming accumulate over C)
BLOCK_G = 256       # output-row tile (a MixOp has G = C rows)
STC_ROWS = 8        # client rows per stc tile (the fp32 sublane count)
VMEM_BUDGET = 2 << 20   # per-operand VMEM budget used to shrink BLOCK_F;
                        # fp32-exact MXU dots also hold split copies of each
                        # tile, and a G = C = 2048 MixOp with 4 MiB operands
                        # overruns the 16 MiB VMEM scope


def stack_ravel(params) -> tuple[jax.Array, tuple]:
    """Flatten a client-stacked pytree to one (C, F) fp32 block.

    Every leaf (C, *shape) is raveled to (C, n) and concatenated on the
    feature axis — the layout :func:`mix_aggregate_pallas` streams through
    VMEM in a single HBM pass.  Returns ``(flat, spec)``;
    :func:`stack_unravel` inverts (restoring leaf shapes and dtypes).
    The concatenate is only worth its copy where the kernel runs (one pass
    over HBM beats L separate per-leaf passes); the XLA reference path in
    ``ops.mix_aggregate_tree`` therefore keeps the per-leaf chain instead.
    """
    leaves, treedef = jax.tree.flatten(params)
    c = leaves[0].shape[0]
    flat = jnp.concatenate(
        [x.reshape(c, -1).astype(jnp.float32) for x in leaves], axis=1)
    meta = tuple((x.shape[1:], x.dtype) for x in leaves)
    return flat, (treedef, meta)


def stack_unravel(flat: jax.Array, spec: tuple, *, collapse: bool = False,
                  keep_float32: bool = False):
    """Inverse of :func:`stack_ravel`.

    ``flat`` may carry any leading slot count G (a (C, F) mixed fleet, an
    (nl, F) shard block, or a (1, F) Eq.-11 aggregate).  ``collapse=True``
    drops the leading axis (requires G=1) — explicit, because a legitimate
    one-slot MixOp also has G=1 and must stay stacked.  ``keep_float32``
    skips the restore to each leaf's stored dtype (for partials that still
    cross a reduction).
    """
    treedef, meta = spec
    g = flat.shape[0]
    if collapse:
        assert g == 1, g
    leaves, off = [], 0
    for shape, dtype in meta:
        n = 1
        for d in shape:
            n *= d
        blk = flat[:, off:off + n]
        off += n
        blk = (blk.reshape(shape) if collapse
               else blk.reshape((g,) + shape))
        leaves.append(blk if keep_float32 else blk.astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def _feature_block(rows: int, block: int, n: int) -> int:
    """Largest lane-aligned feature tile with (rows, tile) under budget."""
    cap = max(128, VMEM_BUDGET // (4 * max(rows, 1)))
    b = min(block, cap, max(128, n))
    return max(128, (b // 128) * 128)


# ------------------------------------------------------------ mix/aggregate

def _mix_kernel(w_ref, x_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)             # (BG, BC)
    x = x_ref[...].astype(jnp.float32)             # (BC, BF)
    # HIGHEST: an fp32 weighted sum on the MXU, not one bf16 pass (which
    # would round every aggregated parameter to ~3 significant digits).
    o_ref[...] += jax.lax.dot(w, x, precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_f", "block_c", "interpret"))
def mix_aggregate_pallas(x: jax.Array, w: jax.Array, *,
                         block_f: int = BLOCK_F, block_c: int = BLOCK_C,
                         interpret: bool = False) -> jax.Array:
    """``w @ x`` over (output-row, feature, client) tiles: x (C, F), w (G, C)
    → (G, F).

    Grid cell (r, i, k) streams the (BC, BF) client tile through VMEM and
    accumulates its Wᵀ-partial into the *revolving* (BG, BF) output block:
    the output index map ignores k, so the block stays resident in VMEM
    across the inner client loop while Pallas double-buffers the next x
    tile's HBM fetch behind the current MXU pass — Eq. (10)/(11) streams
    over fleets far larger than VMEM instead of barriering on one (C, BF)
    slab.  Fleets with C ≤ block_c keep the single-tile schedule (and its
    exact summation order) with a tile as tall as the fleet.  A full MixOp
    of a large fleet (G = C = 2048) tiles its output rows too, so the
    weight block stays within VMEM.
    """
    c, f = x.shape
    g = w.shape[0]
    assert w.shape == (g, c), (w.shape, x.shape)
    # A fleet of at most block_c clients is one client tile spanning the
    # whole axis (a block dim equal to the array dim needs no alignment),
    # so the fleet is never copied to pad it.  Larger fleets stream
    # block_c-row tiles; zero client rows / weight columns contribute
    # nothing to any sum.
    bc = c if c <= block_c else block_c
    pad_c = (-c) % bc
    if pad_c:
        x = jnp.pad(x, ((0, pad_c), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad_c)))
    nc = x.shape[0] // bc
    bg = g if g <= BLOCK_G else BLOCK_G
    bf = _feature_block(max(bc, bg), block_f, f)
    # The last output-row and feature tiles may overhang G and F: output
    # (row, column) depends on weight row and input column alone, so the
    # overhang's unspecified reads land only in output cells that are never
    # written back.
    grid = (pl.cdiv(g, bg), pl.cdiv(f, bf), nc)
    return pl.pallas_call(
        _mix_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bg, bc), lambda r, i, k: (r, k)),
                  pl.BlockSpec((bc, bf), lambda r, i, k: (k, i))],
        out_specs=pl.BlockSpec((bg, bf), lambda r, i, k: (r, i)),
        out_shape=jax.ShapeDtypeStruct((g, f), jnp.float32),
        interpret=interpret,
        name="mix_aggregate",
    )(w.astype(jnp.float32), x.astype(jnp.float32))


# ------------------------------------------------------------------ stc rows

def _stc_reduce_kernel(x_ref, r_ref, thr_ref, sum_ref, cnt_ref, *,
                       n_valid: int, block: int):
    # Two-bank revolving accumulator: even feature tiles land in bank 0,
    # odd tiles in bank 1, so consecutive grid steps extend *independent*
    # serial FP-add chains (the banks are summed on the host side).  That
    # halves the loop-carried latency the pipeline must hide while the
    # next x tile streams in.  Each grid cell holds STC_ROWS client rows
    # and reduces them row by row into (rows, 2) bank columns.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    d = x_ref[...].astype(jnp.float32) - r_ref[...].astype(jnp.float32)
    idx = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    keep = jnp.logical_and(jnp.abs(d) >= thr_ref[...], idx < n_valid)
    bank = (jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
            == j % 2).astype(jnp.float32)
    sum_ref[...] += jnp.sum(jnp.where(keep, jnp.abs(d), 0.0), axis=1,
                            keepdims=True) * bank
    cnt_ref[...] += jnp.sum(keep.astype(jnp.float32), axis=1,
                            keepdims=True) * bank


def _stc_apply_kernel(x_ref, r_ref, thr_ref, mu_ref, mask_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    d = x - r
    tern = jnp.where(jnp.abs(d) >= thr_ref[...], jnp.sign(d) * mu_ref[...],
                     0.0)
    o_ref[...] = jnp.where(mask_ref[...] != 0, r + tern, x)


@functools.partial(jax.jit, static_argnames=("sparsity", "block",
                                             "interpret"))
def stc_rows_pallas(x: jax.Array, ref_row: jax.Array, mask: jax.Array,
                    sparsity: float, *, block: int = BLOCK_F,
                    interpret: bool = False) -> jax.Array:
    """Masked per-row STC against a shared reference row.

    x (C, n); ref_row (n,) — the broadcast global every PUE holds; mask
    (C,) bool.  Row c with ``mask[c]`` becomes ``ref + STC(x_c − ref)``
    (the compressed D2D payload), other rows pass through bit-untouched.
    The top-k threshold is an XLA per-row sort (a quantile serializes a
    Pallas grid — see kernels/stc_compress.py); the survivor reduction and
    the fused ternarize+blend are tiled passes over (STC_ROWS, block)
    tiles, C padded up to a tile multiple with inert rows (mask 0).
    """
    c, n = x.shape
    k = max(1, int(n * sparsity))
    delta = x.astype(jnp.float32) - ref_row.astype(jnp.float32)[None, :]
    thr = jnp.sort(jnp.abs(delta), axis=1)[:, n - k]            # (C,)

    rows = STC_ROWS
    blk = _feature_block(rows, block, n)
    pad = (-n) % blk
    pad_c = (-c) % rows
    xp = jnp.pad(x.astype(jnp.float32), ((0, pad_c), (0, pad)))
    rp = jnp.pad(ref_row.astype(jnp.float32), (0, pad)).reshape(1, -1)
    cp, nb = xp.shape[0], xp.shape[1] // blk
    thr2 = jnp.pad(thr, (0, pad_c)).reshape(cp, 1)
    row_blk = pl.BlockSpec((rows, blk), lambda i, j: (i, j))
    ref_blk = pl.BlockSpec((1, blk), lambda i, j: (0, j))
    col_blk = pl.BlockSpec((rows, 1), lambda i, j: (i, 0))
    bank_blk = pl.BlockSpec((rows, 2), lambda i, j: (i, 0))
    reduce_kernel = functools.partial(_stc_reduce_kernel, n_valid=n,
                                      block=blk)
    ssum, cnt = pl.pallas_call(
        reduce_kernel,
        grid=(cp // rows, nb),
        in_specs=[row_blk, ref_blk, col_blk],
        out_specs=[bank_blk, bank_blk],
        out_shape=[jax.ShapeDtypeStruct((cp, 2), jnp.float32),
                   jax.ShapeDtypeStruct((cp, 2), jnp.float32)],
        interpret=interpret,
        name="stc_rows_reduce",
    )(xp, rp, thr2)
    ssum = ssum.sum(axis=1, keepdims=True)                      # (Cp, 1)
    cnt = cnt.sum(axis=1, keepdims=True)
    mu = ssum / jnp.maximum(cnt, 1.0)                           # (Cp, 1)
    mask2 = jnp.pad(mask.astype(jnp.int32), (0, pad_c)).reshape(cp, 1)
    out = pl.pallas_call(
        _stc_apply_kernel,
        grid=(cp // rows, nb),
        in_specs=[row_blk, ref_blk, col_blk, col_blk, col_blk],
        out_specs=row_blk,
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.float32),
        interpret=interpret,
        name="stc_rows_apply",
    )(xp, rp, thr2, mu, mask2)
    return out[:c, :n].astype(x.dtype)


# ------------------------------------------------------------ dol bid scores

def _center_stats(dol, chain_size, dsi, data_size):
    """Centered operands + row/col statistics of the fused expansion."""
    m, c = dol.shape
    u = 1.0 / c
    psi_c = dol.astype(jnp.float32) - u                       # (M, C)
    d_c = dsi.astype(jnp.float32) - u                         # (N, C)
    a = chain_size.astype(jnp.float32).reshape(m, 1)          # (M, 1)
    b = data_size.astype(jnp.float32).reshape(-1, 1)          # (N, 1)
    p_psi = jnp.sum(psi_c * psi_c, axis=1, keepdims=True)     # (M, 1)
    s_psi = jnp.sum(psi_c, axis=1, keepdims=True)             # (M, 1)
    p_d = jnp.sum(d_c * d_c, axis=1, keepdims=True)           # (N, 1)
    s_d = jnp.sum(d_c, axis=1, keepdims=True)                 # (N, 1)
    return psi_c, d_c, a, b, p_psi, s_psi, p_d, s_d


def _bid_scores_from_stats(cross, a, b, p_psi, s_psi, p_d, s_d, u):
    """dist²(cand, U) from the centered statistics; see module docstring."""
    bt = b.reshape(1, -1)                                     # (1, N)
    p_dt = p_d.reshape(1, -1)
    s_dt = s_d.reshape(1, -1)
    s = a + bt                                                # (M, N)
    sp = jnp.maximum(s, 1.0)
    delta = s / sp - 1.0                                      # 0 when s ≥ 1
    core = (a * a * p_psi + 2.0 * a * bt * cross
            + bt * bt * p_dt) / (sp * sp)
    lin = 2.0 * u * delta * (a * s_psi + bt * s_dt) / sp
    quad = (1.0 / u) * (u * delta) ** 2                       # C·u²·δ²
    return jnp.sqrt(jnp.maximum(core + lin + quad, 0.0))


def dol_bid_scores_xla_fused(dol: jax.Array, chain_size: jax.Array,
                             dsi: jax.Array, data_size: jax.Array
                             ) -> jax.Array:
    """Pure-jnp twin of the kernel math (w1_norm metric).

    Identical algebra — one (M, C)×(C, N) contraction, no (M, N, C)
    broadcast — so it is both the kernel's parity oracle and the fast XLA
    path for large-N planning on backends without Pallas.
    """
    psi_c, d_c, a, b, p_psi, s_psi, p_d, s_d = _center_stats(
        dol, chain_size, dsi, data_size)
    cross = psi_c @ d_c.T                                     # (M, N)
    return _bid_scores_from_stats(cross, a, b, p_psi, s_psi, p_d, s_d,
                                  1.0 / dol.shape[1])


def _bid_kernel(psi_ref, a_ref, ppsi_ref, spsi_ref,
                d_ref, b_ref, pd_ref, sd_ref, o_ref, *, u: float):
    psi = psi_ref[...].astype(jnp.float32)                    # (BM, C)
    d = d_ref[...].astype(jnp.float32)                        # (BN, C)
    cross = jax.lax.dot_general(
        psi, d, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                   # (BM, BN)
    o_ref[...] = _bid_scores_from_stats(
        cross, a_ref[...], b_ref[...].reshape(1, -1),
        ppsi_ref[...], spsi_ref[...],
        pd_ref[...].reshape(1, -1), sd_ref[...].reshape(1, -1), u)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def dol_bid_scores_pallas(dol: jax.Array, chain_size: jax.Array,
                          dsi: jax.Array, data_size: jax.Array, *,
                          block_m: int = 128, block_n: int = 256,
                          interpret: bool = False) -> jax.Array:
    """Candidate IID-distance matrix (M, N) on the MXU, tiled over (M, N).

    Grid cell (i, j) loads the centered (BM, C) DoL block and (BN, C) DSI
    block, contracts them once, and finishes with rank-1 statistics — the
    (M, N, C) candidate tensor never exists in HBM.  w1_norm metric (the
    paper's Eq. B.1 default); other metrics fall back to the reference
    composite in ``kernels.ops``.
    """
    m, c = dol.shape
    n = dsi.shape[0]
    psi_c, d_c, a, b, p_psi, s_psi, p_d, s_d = _center_stats(
        dol, chain_size, dsi, data_size)
    bm = min(block_m, max(8, -(-m // 8) * 8))
    bn = min(block_n, max(128, -(-n // 128) * 128))
    pm, pn = (-m) % bm, (-n) % bn
    pad_m = lambda t: jnp.pad(t, ((0, pm), (0, 0)))     # noqa: E731
    pad_n = lambda t: jnp.pad(t, ((0, pn), (0, 0)))     # noqa: E731
    psi_c, a, p_psi, s_psi = map(pad_m, (psi_c, a, p_psi, s_psi))
    d_c, b, p_d, s_d = map(pad_n, (d_c, b, p_d, s_d))
    grid = (psi_c.shape[0] // bm, d_c.shape[0] // bn)
    kernel = functools.partial(_bid_kernel, u=1.0 / c)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, c), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, c), lambda i, j: (j, 0)),
                  pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((bn, 1), lambda i, j: (j, 0)),
                  pl.BlockSpec((bn, 1), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((psi_c.shape[0], d_c.shape[0]),
                                       jnp.float32),
        interpret=interpret,
        name="dol_bid_scores",
    )(psi_c, a, p_psi, s_psi, d_c, b, p_d, s_d)
    return out[:m, :n]


# ------------------------------------------------------------ bid value fuse

def _bid_value_kernel(bids_ref, val_ref, w_ref, o_ref):
    o_ref[...] = bids_ref[...] * (1.0 + w_ref[0, 0] * val_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def bid_value_fuse_pallas(bids: jax.Array, value: jax.Array,
                          weight: jax.Array | float, *,
                          block_m: int = 128, block_n: int = 256,
                          interpret: bool = False) -> jax.Array:
    """Fuse the per-client learning value into the (M, N) bid matrix.

    Elementwise VPU tile: grid cell (i, j) scales its bid block by
    ``1 + w · value`` with the value row broadcast down the model axis —
    the companion of ``dol_bid_scores_pallas`` in the planner's auction
    surface.  Semantics of record: ``kernels.ref.bid_value_fuse_ref``.
    """
    m, n = bids.shape
    bids32 = bids.astype(jnp.float32)
    val = value.astype(jnp.float32).reshape(1, n)
    w = jnp.asarray(weight, jnp.float32).reshape(1, 1)
    bm = min(block_m, max(8, -(-m // 8) * 8))
    bn = min(block_n, max(128, -(-n // 128) * 128))
    pm, pn = (-m) % bm, (-n) % bn
    bp = jnp.pad(bids32, ((0, pm), (0, pn)))
    vp = jnp.pad(val, ((0, 0), (0, pn)))
    grid = (bp.shape[0] // bm, bp.shape[1] // bn)
    out = pl.pallas_call(
        _bid_value_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(bp.shape, jnp.float32),
        interpret=interpret,
        name="bid_value_fuse",
    )(bp, vp, w)
    return out[:m, :n]
