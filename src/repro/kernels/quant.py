"""Int8 absmax quantization as a Pallas kernel pair — the adapter hop wire.

The FedDif hop payload (the trainable-adapter view of a client model,
``repro.fl.adapters``) is packed per row-block before every PermuteOp move:

  pack   (``_pack_kernel``):   per row of a (rows, block) tile, ``scale =
         max(absmax, ε)/127`` and ``q = clip(round(x/scale), ±127)`` int8;
  unpack (``_unpack_kernel``): ``q·scale`` back to fp32 at the destination.

One fp32 scale per block-row rides along with the int8 payload, so a packed
hop costs ``8·block + 32`` bits per row against ``32·block`` for fp32 — the
4x the Eq.-15 ledger charges via ``spec_adapter_bits``.  All-zero rows hit
the ε floor and quantize to exact zeros, which keeps padded mesh slots inert.
Each grid cell holds ROW_TILE row-blocks of 512 elements (a multiple of the
int8 sublane tile, 32: the TPU compiler tiles an int8 block by (32, 128) and
refuses a (1, 512) one), or all R rows when R is smaller.  A row's scale and
codes depend on that row alone, so the last tile may overhang R: its
overhanging rows are never written back, and the payload is never copied to
pad it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quant_pack_pallas", "quant_unpack_pallas", "QUANT_BLOCK"]

QUANT_BLOCK = 512   # elements per quantization row-block (fp32: 2 KB)

_EPS = 1e-12        # absmax floor: all-zero rows stay exactly zero

ROW_TILE = 256      # rows per grid cell (256 x 512 fp32 = 512 KB in VMEM)


def _pack_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                       # (rows, block)
    # multiply by the fp32 reciprocal, NOT /127.0: XLA lowers constant
    # division to a reciprocal multiply only on some paths, and the 1-ulp
    # scale drift would break ref/pallas bitwise wire parity
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                        _EPS) * jnp.float32(1 / 127)         # (rows, 1)
    s_ref[...] = scale
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(
        jnp.int8)


def _unpack_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _row_tile(r: int) -> int:
    """Rows per grid cell: all of them, or ROW_TILE with a ragged last tile."""
    return r if r <= ROW_TILE else ROW_TILE


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_pack_pallas(x: jax.Array, *, interpret: bool = False):
    """x (R, B) fp32 → (q (R, B) int8, scale (R,) fp32), absmax per row."""
    r, b = x.shape
    tr = _row_tile(r)
    q, s = pl.pallas_call(
        _pack_kernel,
        grid=(pl.cdiv(r, tr),),
        in_specs=[pl.BlockSpec((tr, b), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tr, b), lambda i: (i, 0)),
                   pl.BlockSpec((tr, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, b), jnp.int8),
                   jax.ShapeDtypeStruct((r, 1), jnp.float32)],
        interpret=interpret,
    )(x.astype(jnp.float32))
    return q, s[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_unpack_pallas(q: jax.Array, scale: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """(q (R, B) int8, scale (R,)) → (R, B) fp32 dequantized payload."""
    r, b = q.shape
    tr = _row_tile(r)
    return pl.pallas_call(
        _unpack_kernel,
        grid=(pl.cdiv(r, tr),),
        in_specs=[pl.BlockSpec((tr, b), lambda i: (i, 0)),
                  pl.BlockSpec((tr, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, b), jnp.float32),
        interpret=interpret,
    )(q, scale.astype(jnp.float32).reshape(r, 1))
