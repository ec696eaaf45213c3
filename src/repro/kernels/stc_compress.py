"""Sparse Ternary Compression as a two-pass Pallas TPU kernel pipeline.

STC (the paper's Table-II compression baseline, Sattler et al. [41]) maps a
tensor to ``μ·sign(x)·1[|x| ≥ τ]`` with τ the top-k magnitude threshold and
μ the mean magnitude of the survivors.  On TPU this runs as:

  pass 1 (``_reduce_kernel``): tiled reduction computing, per VMEM block,
          ``(Σ |x|·1[|x|≥τ], Σ 1[|x|≥τ])`` — accumulated across the
          sequential grid in SMEM-like (1,1) accumulator tiles;
  pass 2 (``_apply_kernel``):  tiled elementwise ternarize with the final μ.

τ itself is a quantile — a global sort that XLA already does well (and that
would serialize a Pallas grid), so ``ops.stc_compress`` computes it with
``jnp.quantile`` and hands it to the kernels as a scalar operand.  Block
size 64k elements = 256 KB fp32 per buffer in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["stc_reduce_pallas", "stc_apply_pallas"]

BLOCK = 65536   # elements per tile (fp32: 256 KB in VMEM)


def _reduce_kernel(x_ref, thr_ref, sum_ref, cnt_ref, *, n_valid: int,
                   block: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...].astype(jnp.float32)                      # (1, block)
    idx = i * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    keep = jnp.logical_and(jnp.abs(x) >= thr_ref[0, 0], idx < n_valid)
    mag = jnp.where(keep, jnp.abs(x), 0.0)
    sum_ref[...] += jnp.sum(mag).reshape(1, 1)
    cnt_ref[...] += jnp.sum(keep.astype(jnp.float32)).reshape(1, 1)


def _apply_kernel(x_ref, thr_ref, mu_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    keep = jnp.abs(x) >= thr_ref[0, 0]
    o_ref[...] = jnp.where(keep, jnp.sign(x) * mu_ref[0, 0], 0.0).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def stc_reduce_pallas(flat: jax.Array, thr: jax.Array, *,
                      block: int = BLOCK, interpret: bool = False):
    """Returns (Σ|x| over survivors, #survivors) for a flat fp32 array."""
    n = flat.shape[0]
    block = min(block, max(128, n))
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    grid = flat.shape[0] // block
    x2 = flat.reshape(grid, block)
    thr2 = thr.reshape(1, 1).astype(jnp.float32)
    kernel = functools.partial(_reduce_kernel, n_valid=n, block=block)
    s, c = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interpret,
    )(x2, thr2)
    return s[0, 0], c[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def stc_apply_pallas(flat: jax.Array, thr: jax.Array, mu: jax.Array, *,
                     block: int = BLOCK, interpret: bool = False):
    n = flat.shape[0]
    block = min(block, max(128, n))
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    grid = flat.shape[0] // block
    x2 = flat.reshape(grid, block)
    out = pl.pallas_call(
        _apply_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid, block), flat.dtype),
        interpret=interpret,
    )(x2, thr.reshape(1, 1).astype(jnp.float32),
      mu.reshape(1, 1).astype(jnp.float32))
    return out.reshape(-1)[:n]
