"""Attention: GQA/MHA with RoPE, qk-norm, sliding-window, chunked prefill and
single-token decode with a KV cache.

Prefill uses a two-level chunked online-softmax (lax.map over query chunks,
lax.scan over KV chunks) so a 32k context never materializes an (S, S) score
matrix.  Sliding-window layers slice only ``window + q_chunk`` keys per query
chunk (true FLOP reduction); full-causal layers mask (XLA computes the full
rectangle — the Pallas kernel in ``repro.kernels.flash_attention`` skips
non-causal blocks on real TPUs; see EXPERIMENTS.md §Roofline for the
accounting).

Decode attends one query token against the whole cache in a single einsum;
with the cache sequence-sharded over the ``model`` mesh axis the softmax
reduction lowers to the flash-decoding-style cross-device combine
automatically (XLA SPMD inserts the all-reduce over partial stats).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

Array = jax.Array
Params = Any

__all__ = ["AttnSpec", "init_attention", "attn_forward", "init_kv_cache",
           "attn_decode", "chunked_attention", "precompute_cross_kv",
           "cross_attn_decode"]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    use_rope: bool = True
    rope_theta: float = 10000.0
    causal: bool = True
    window: Optional[int] = None        # sliding-window width in tokens
    q_chunk: int = 256
    kv_chunk: int = 512
    norm_eps: float = 1e-6
    compute_dtype: Any = jnp.bfloat16

    @property
    def q_groups(self) -> int:
        assert self.num_heads % self.num_kv_heads == 0
        return self.num_heads // self.num_kv_heads


def init_attention(key, spec: AttnSpec) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hd = spec.d_model, spec.head_dim
    p = {
        "wq": L.init_dense(kq, d, spec.num_heads * hd),
        "wk": L.init_dense(kk, d, spec.num_kv_heads * hd),
        "wv": L.init_dense(kv, d, spec.num_kv_heads * hd),
        "wo": L.init_dense(ko, spec.num_heads * hd, d),
    }
    if spec.qk_norm:
        p["q_norm"] = L.init_rmsnorm(hd)
        p["k_norm"] = L.init_rmsnorm(hd)
    return p


def _project_qkv(p: Params, spec: AttnSpec, x: Array,
                 positions: Array | None):
    """Returns q (B,S,KH,G,Dh), k (B,S,KH,Dh), v (B,S,KH,Dh)."""
    b, s, _ = x.shape
    cd = spec.compute_dtype
    q = L.dense(p["wq"], x, cd).reshape(b, s, spec.num_heads, spec.head_dim)
    k = L.dense(p["wk"], x, cd).reshape(b, s, spec.num_kv_heads, spec.head_dim)
    v = L.dense(p["wv"], x, cd).reshape(b, s, spec.num_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, spec.norm_eps)
        k = L.rmsnorm(p["k_norm"], k, spec.norm_eps)
    if spec.use_rope:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        cos, sin = L.rope_freqs(spec.head_dim, spec.rope_theta, positions)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    q = q.reshape(b, s, spec.num_kv_heads, spec.q_groups, spec.head_dim)
    return q, k, v


def _chunk_attend(q_blk: Array, k_blk: Array, v_blk: Array, mask: Array,
                  m_prev: Array, l_prev: Array, o_prev: Array, scale: float):
    """One online-softmax update.

    q_blk: (B,Tq,KH,G,Dh)  k_blk/v_blk: (B,Tk,KH,Dh)
    mask:  (Tq,Tk) True = attend
    state: m/l (B,KH,G,Tq), o (B,Tq,KH,G,Dh); fp32.
    """
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk).astype(jnp.float32)
    s = s * scale + jnp.where(mask, 0.0, NEG_INF)[None, None, None]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1.
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(mask[None, None, None], p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp(m_prev - safe_m))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd",
                    p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
    o_new = alpha.transpose(0, 3, 1, 2)[..., None] * o_prev + pv
    return m_new, l_new, o_new


def chunked_attention(q: Array, k: Array, v: Array, spec: AttnSpec,
                      q_offset: int = 0) -> Array:
    """Causal / sliding-window attention over (possibly long) sequences.

    q: (B,Sq,KH,G,Dh), k: (B,Sk,KH,Dh), v: (B,Sk,KH,Dv); Dv may differ
    from Dh (latent attention).  Returns (B,Sq,KH*G,Dv).
    ``q_offset``: absolute position of q[0] within the kv sequence (used by
    cross-shaped prefill; 0 for self-attention where Sq == Sk).
    """
    b, sq, kh, g, dh = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    scale = 1.0 / (dh ** 0.5)
    qc = min(spec.q_chunk, sq)
    kc = min(spec.kv_chunk, sk)
    # Pad to chunk multiples.
    pad_q = (-sq) % qc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    nq = q.shape[1] // qc

    window = spec.window

    def per_q_chunk(qi):
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, axis=1)
        q_pos = q_offset + qi * qc + jnp.arange(qc)
        m0 = jnp.full((b, kh, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qc), jnp.float32)
        o0 = jnp.zeros((b, qc, kh, g, dv), jnp.float32)

        if window is not None:
            # Only the last (window + qc) keys can be visible to this chunk.
            # Rematerialized: the VJP recomputes scores instead of saving the
            # (qc, span) probability block per chunk.
            span = min(window + qc, sk)

            @functools.partial(jax.checkpoint, prevent_cse=False)
            def windowed(q_blk, qi):
                start = jnp.clip(q_offset + qi * qc + qc - span, 0, sk - span)
                k_blk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
                v_blk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
                k_pos = start + jnp.arange(span)
                q_pos_in = q_offset + qi * qc + jnp.arange(qc)
                mask = (k_pos[None, :] <= q_pos_in[:, None]) & \
                       (k_pos[None, :] > q_pos_in[:, None] - window)
                return _chunk_attend(q_blk, k_blk, v_blk, mask, m0, l0, o0,
                                     scale)

            m, l, o = windowed(q_blk, qi)
        else:
            nk = -(-sk // kc)
            pad_k = nk * kc - sk
            k_pad = (jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
                     if pad_k else k)
            v_pad = (jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
                     if pad_k else v)

            # Rematerialized per KV block: k_pad/v_pad are closure constants
            # (saved once), so the scan VJP keeps only the small (m, l, o)
            # carries and recomputes each score block — flash-attention-style
            # memory in pure XLA.
            @functools.partial(jax.checkpoint, prevent_cse=False)
            def kv_body(carry, ki):
                m, l, o = carry
                k_blk = jax.lax.dynamic_slice_in_dim(k_pad, ki * kc, kc,
                                                     axis=1)
                v_blk = jax.lax.dynamic_slice_in_dim(v_pad, ki * kc, kc,
                                                     axis=1)
                k_pos = ki * kc + jnp.arange(kc)
                valid = k_pos[None, :] < sk
                if spec.causal:
                    mask = (k_pos[None, :] <= q_pos[:, None]) & valid
                else:
                    mask = jnp.broadcast_to(valid, (qc, kc))
                m, l, o = _chunk_attend(q_blk, k_blk, v_blk, mask, m, l, o,
                                        scale)
                return (m, l, o), None

            (m, l, o), _ = jax.lax.scan(kv_body, (m0, l0, o0),
                                        jnp.arange(nk))
        l_t = jnp.maximum(l.transpose(0, 3, 1, 2)[..., None], 1e-20)
        return (o / l_t).astype(spec.compute_dtype)   # (B,qc,KH,G,Dv)

    out = jax.lax.map(per_q_chunk, jnp.arange(nq))    # (nq,B,qc,KH,G,Dv)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * qc, kh, g, dv)
    out = out[:, :sq]
    return out.reshape(b, sq, kh * g, dv)


def attn_forward(p: Params, spec: AttnSpec, x: Array,
                 positions: Array | None = None,
                 context: Array | None = None) -> Array:
    """Self-attention (context=None) or cross-attention (context=(B,Sc,D))."""
    b, s, _ = x.shape
    cd = spec.compute_dtype
    if context is None:
        q, k, v = _project_qkv(p, spec, x, positions)
    else:
        sc = context.shape[1]
        q = L.dense(p["wq"], x, cd).reshape(b, s, spec.num_heads,
                                            spec.head_dim)
        k = L.dense(p["wk"], context, cd).reshape(b, sc, spec.num_kv_heads,
                                                  spec.head_dim)
        v = L.dense(p["wv"], context, cd).reshape(b, sc, spec.num_kv_heads,
                                                  spec.head_dim)
        if spec.qk_norm:
            q = L.rmsnorm(p["q_norm"], q, spec.norm_eps)
            k = L.rmsnorm(p["k_norm"], k, spec.norm_eps)
        q = q.reshape(b, s, spec.num_kv_heads, spec.q_groups, spec.head_dim)
    out = chunked_attention(q, k, v, spec)
    out = out.reshape(b, s, spec.num_heads * spec.head_dim)
    return L.dense(p["wo"], out, cd)


# ---------------------------------------------------------------- decode

def init_kv_cache(spec: AttnSpec, batch: int, max_seq: int,
                  dtype=None) -> Params:
    dtype = spec.compute_dtype if dtype is None else dtype
    shape = (batch, max_seq, spec.num_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(p: Params, spec: AttnSpec, x: Array, cache: Params,
                pos: Array, ring: bool = False) -> tuple[Array, Params]:
    """One-token decode. x: (B, 1, D); pos: scalar int32 (current length)
    or a per-row (B,) vector — ragged continuous batching (serving engine)
    decodes slots at different sequence positions in one call.

    Linear mode writes the new K/V at ``pos`` and attends to ``cache[:pos+1]``
    via mask.  Ring mode (sliding-window layers) treats the cache as a ring
    buffer of length L: slot ``pos % L`` is overwritten and slot ``ri`` holds
    absolute position ``pos − ((pos − ri) mod L)``.
    """
    b = x.shape[0]
    cd = spec.compute_dtype
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    positions = pos_vec[:, None]
    q, k_new, v_new = _project_qkv(p, spec, x, positions)
    s_max = cache["k"].shape[1]
    write_pos = jnp.remainder(pos_vec, s_max) if ring else pos_vec
    upd = jax.vmap(
        lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, axis=0))
    cache_k = upd(cache["k"], k_new.astype(cache["k"].dtype), write_pos)
    cache_v = upd(cache["v"], v_new.astype(cache["v"].dtype), write_pos)
    kpos = jnp.arange(s_max)
    pv = pos_vec[:, None]
    if ring:
        abs_pos = pv - jnp.remainder(pv - kpos[None, :], s_max)   # (B, S)
        mask = abs_pos >= 0
        if spec.window is not None:
            mask &= abs_pos > pv - spec.window
    else:
        mask = kpos[None, :] <= pv
        if spec.window is not None:
            mask &= kpos[None, :] > pv - spec.window
    scale = 1.0 / (spec.head_dim ** 0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q,
                        cache_k.astype(cd)).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(cd)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, cache_v.astype(cd))
    out = out.reshape(b, 1, spec.num_heads * spec.head_dim)
    y = L.dense(p["wo"], out, cd)
    return y, {"k": cache_k, "v": cache_v}


def precompute_cross_kv(p: Params, spec: AttnSpec, context: Array) -> Params:
    """Project the encoder output once into a static cross-attention cache."""
    b, sc, _ = context.shape
    cd = spec.compute_dtype
    k = L.dense(p["wk"], context, cd).reshape(b, sc, spec.num_kv_heads,
                                              spec.head_dim)
    v = L.dense(p["wv"], context, cd).reshape(b, sc, spec.num_kv_heads,
                                              spec.head_dim)
    return {"k": k, "v": v}


def cross_attn_decode(p: Params, spec: AttnSpec, x: Array,
                      context_cache: Params) -> Array:
    """One-token cross-attention against a precomputed encoder KV cache."""
    b = x.shape[0]
    cd = spec.compute_dtype
    kc = context_cache["k"].astype(cd)
    vc = context_cache["v"].astype(cd)
    q = L.dense(p["wq"], x, cd).reshape(b, 1, spec.num_kv_heads,
                                        spec.q_groups, spec.head_dim)
    scale = 1.0 / (spec.head_dim ** 0.5)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, kc).astype(jnp.float32) * scale
    pr = jax.nn.softmax(s, axis=-1).astype(cd)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, vc)
    return L.dense(p["wo"], o.reshape(b, 1, -1), cd)
