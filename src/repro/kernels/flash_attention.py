"""Flash attention as a Pallas TPU kernel.

Blockwise online-softmax attention with explicit VMEM tiling:

* grid = (batch·heads, q_blocks, kv_blocks) — the kv axis is innermost, so
  the fp32 (m, l, acc) running-softmax state lives in VMEM scratch across kv
  iterations (TPU grids execute sequentially over the last axis).
* BlockSpec tiles: q/o (1, block_q, head_dim); k/v (1, block_k, head_dim) —
  block sizes default to (256, 512), MXU-aligned multiples of 128 chosen so
  the working set (q + k + v + acc ≈ 0.6 MB at d=128) sits comfortably in
  the ~16 MB/core VMEM with room for double-buffering.
* causal/sliding-window masking is applied in-kernel; fully-masked kv blocks
  are skipped with ``pl.when`` (on real TPUs this prunes ~half the FLOPs of
  a causal prefill — the XLA fallback cannot skip; see §Roofline).

Validated in interpret mode against ``ref.flash_attention_ref`` over shape /
dtype / window sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

__all__ = ["flash_attention_pallas"]


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, block_q: int, block_k: int, seq_q: int,
                 seq_k: int, causal: bool, window: int | None,
                 num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Absolute token positions of this tile (q right-aligned to kv end).
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (seq_k - seq_q)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Block-level visibility: skip tiles that are fully masked.
    q_blk_max = qi * block_q + block_q - 1 + (seq_k - seq_q)
    q_blk_min = qi * block_q + (seq_k - seq_q)
    k_blk_min = ki * block_k
    k_blk_max = ki * block_k + block_k - 1
    visible = jnp.asarray(True)
    if causal:
        visible = jnp.logical_and(visible, k_blk_min <= q_blk_max)
    if window is not None:
        visible = jnp.logical_and(visible, k_blk_max > q_blk_min - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = k_pos < seq_k
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - safe_m))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        acc_scr[...] = alpha[:, None] * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret", "scale"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           scale: float | None = None, block_q: int = 256,
                           block_k: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, H, D) — same head count (pre-repeated
    for GQA by the caller).  Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = float(1.0 / (d ** 0.5)) if scale is None else float(scale)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    # fold heads into batch; pad seq to block multiples
    def fold(x, s):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)
        return x

    qf, kf, vf = fold(q, sq), fold(k, sk), fold(v, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    nq = qf.shape[1] // block_q
    nk = kf.shape[1] // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=sq, seq_k=sk, causal=causal, window=window, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * block_q, d), q.dtype),
        scratch_shapes=[
            # fp32 online-softmax state in VMEM, persistent across kv blocks
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :sq].reshape(b, h, sq, d)
    return jnp.moveaxis(out, 1, 2)
