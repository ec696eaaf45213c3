"""Mamba-2 SSD chunk scan as a Pallas TPU kernel (zamba2's hot spot).

Semantics (scalar-decay-per-head state space, Dao & Gu 2024):

    h_t = exp(a_t)[h] · h_{t-1} + x_t[h,p] ⊗ b_t[n]
    y_t[h,p] = Σ_n c_t[n] · h_t[h,p,n]

Blocking — one grid step processes a (chunk × head-block) tile entirely in
VMEM: the intra-chunk contribution is the quadratic-within-chunk form
(C Bᵀ ∘ decay-tril) · X, the inter-chunk contribution flows through the
(head_block, P, N) state scratch that persists across the sequential
seq-chunk grid axis.  Default tile (chunk 128 × 8 heads × P64 × N64) keeps
the fp32 working set ≈ 4.5 MB — half of VMEM with double-buffering room.

Validated in interpret mode against ``ref_ssd.ssd_scan_ref`` (sequential
recurrence oracle) and against the model-layer chunked implementation
(``repro.models.ssm._ssd_chunk_scan``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas", "ssd_scan_ref"]


def ssd_scan_ref(xh: jax.Array, a: jax.Array, bmat: jax.Array,
                 cmat: jax.Array) -> jax.Array:
    """Sequential oracle.  xh (B,S,H,P), a (B,S,H), b/c (B,S,N) -> (B,S,H,P)."""
    b_, s, h, p = xh.shape
    n = bmat.shape[-1]

    def step(hst, xs):
        x_t, a_t, b_t, c_t = xs
        hst = jnp.exp(a_t)[:, :, None, None] * hst \
            + x_t[..., None] * b_t[:, None, None, :]
        y_t = jnp.einsum("bn,bhpn->bhp", c_t, hst)
        return hst, y_t

    h0 = jnp.zeros((b_, h, p, n), jnp.float32)
    _, ys = jax.lax.scan(step, h0,
                         (jnp.moveaxis(xh, 1, 0).astype(jnp.float32),
                          jnp.moveaxis(a, 1, 0).astype(jnp.float32),
                          jnp.moveaxis(bmat, 1, 0).astype(jnp.float32),
                          jnp.moveaxis(cmat, 1, 0).astype(jnp.float32)))
    return jnp.moveaxis(ys, 0, 1)


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, h_scr, *, chunk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)            # (L, hb, P)
    a = a_ref[0].astype(jnp.float32)            # (L, hb)
    b = b_ref[0].astype(jnp.float32)            # (L, N)
    c = c_ref[0].astype(jnp.float32)            # (L, N)
    acum = jnp.cumsum(a, axis=0)                # (L, hb)

    # intra-chunk: y[q] += Σ_k 1[k<=q]·exp(acum_q−acum_k)·(c_q·b_k)·x_k
    rel = acum[:, None, :] - acum[None, :, :]   # (Lq, Lk, hb)
    ltri = jnp.tril(jnp.ones((x.shape[0], x.shape[0]), jnp.bool_))
    dec = jnp.exp(jnp.where(ltri[:, :, None], rel, -1e30))
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Lq, Lk)
    w = cb[:, :, None] * dec                    # (Lq, Lk, hb)
    y_intra = jnp.einsum("qkh,khp->qhp", w, x)

    # inter-chunk: carried state h (hb, P, N)
    h = h_scr[...]
    y_state = jnp.einsum("qn,hpn,qh->qhp", c, h, jnp.exp(acum))
    # state update
    tot = jnp.exp(acum[-1])                     # (hb,)
    decay_k = jnp.exp(acum[-1:, :] - acum)      # (L, hb)
    h_scr[...] = tot[:, None, None] * h + jnp.einsum(
        "kn,khp,kh->hpn", b, x, decay_k)

    y_ref[0] = (y_intra + y_state).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_h", "interpret"))
def ssd_scan_pallas(xh: jax.Array, a: jax.Array, bmat: jax.Array,
                    cmat: jax.Array, *, chunk: int = 128, block_h: int = 8,
                    interpret: bool = False) -> jax.Array:
    """xh (B,S,H,P), a (B,S,H), b/c (B,S,N) -> y (B,S,H,P)."""
    b_, s, h, p = xh.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    block_h = min(block_h, h)
    pad_s = (-s) % chunk
    pad_h = (-h) % block_h
    if pad_s or pad_h:
        xh = jnp.pad(xh, ((0, 0), (0, pad_s), (0, pad_h), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_h)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad_s), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad_s), (0, 0)))
    ns = xh.shape[1] // chunk
    nh = xh.shape[2] // block_h

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b_, nh, ns),
        in_specs=[
            pl.BlockSpec((1, chunk, block_h, p),
                         lambda bi, hi, si: (bi, si, hi, 0)),
            pl.BlockSpec((1, chunk, block_h),
                         lambda bi, hi, si: (bi, si, hi)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, si: (bi, si, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, si: (bi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_h, p),
                               lambda bi, hi, si: (bi, si, hi, 0)),
        out_shape=jax.ShapeDtypeStruct(xh.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_h, p, n), jnp.float32)],
        interpret=interpret,
    )(xh, a, bmat, cmat)
    return y[:, :s, :h]
