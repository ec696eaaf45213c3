"""Chunked selective-scan (diagonal linear recurrence) as a Pallas TPU kernel.

Computes ``h_t = da_t ⊙ h_{t-1} + dbx_t`` over the sequence axis — the inner
recurrence of Mamba-1 (``repro.models.ssm``).  Blocking mirrors the model's
chunked scan, adapted to the TPU memory hierarchy:

* grid = (batch, d_inner blocks, seq chunks) — seq innermost/sequential, so
  the carried state h (block_d, N) persists in VMEM scratch across chunks;
* per grid step the kernel loads a (chunk, block_d, N) tile of da/dbx into
  VMEM (default 128×256×16 fp32 = 2 MB/operand), runs the recurrence with a
  ``fori_loop`` over the chunk, and writes the states tile;
* channel blocks are independent → the d grid axis parallelizes across
  cores, and the `model`-axis sharding of d_inner composes on top.

Validated in interpret mode against ``ref.ssm_scan_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan_pallas"]


def _scan_kernel(da_ref, dbx_ref, h_ref, h_scr, *, chunk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def body(t, h):
        a = da_ref[0, t].astype(jnp.float32)        # (block_d, N)
        bx = dbx_ref[0, t].astype(jnp.float32)
        h = a * h + bx
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, body, h_scr[...])


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan_pallas(da: jax.Array, dbx: jax.Array, *, chunk: int = 128,
                    block_d: int = 256, interpret: bool = False) -> jax.Array:
    """da/dbx: (B, S, D, N) -> all states (B, S, D, N)."""
    b, s, d, n = da.shape
    chunk = min(chunk, s)
    block_d = min(block_d, d)
    pad_s = (-s) % chunk
    pad_d = (-d) % block_d
    if pad_s or pad_d:
        cfg = ((0, 0), (0, pad_s), (0, pad_d), (0, 0))
        da = jnp.pad(da, cfg, constant_values=1.0)
        dbx = jnp.pad(dbx, cfg)
    ns = da.shape[1] // chunk
    nd = da.shape[2] // block_d

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b, nd, ns),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d, n),
                         lambda bi, di, si: (bi, si, di, 0)),
            pl.BlockSpec((1, chunk, block_d, n),
                         lambda bi, di, si: (bi, si, di, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d, n),
                               lambda bi, di, si: (bi, si, di, 0)),
        out_shape=jax.ShapeDtypeStruct(da.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        interpret=interpret,
    )(da, dbx)
    return out[:, :s, :d]
