"""Multi-head latent attention (DeepSeek-V2/V3, Moonlight), without a query
LoRA.

Per token: ``q_proj`` gives each head a query of ``qk_nope_head_dim`` plus
``qk_rope_head_dim``; ``kv_a_proj_with_mqa`` gives a latent of
``kv_lora_rank`` and one rope key shared by every head; the latent passes
an RMSNorm (``kv_a_layernorm``) and ``kv_b_proj`` expands it to each head's
key (``qk_nope_head_dim``) and value (``v_head_dim``).  Keys are the
expanded part joined to the shared rope key; the scale is
``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``.  Rope rotates interleaved
pairs, as the published checkpoints lay them out: the rope parts are
de-interleaved (even lanes, then odd) and given the half-split rotation of
``layers.apply_rope``; queries and keys move alike, so scores are those of
the interleaved rotation.

Training runs over full sequences through ``attention.chunked_attention``
(queries and keys of ``nope + rope``, values of ``v_head_dim``).  Decode
caches the normed latent and the rotated rope key, 576 numbers a token at
the published widths, and expands the cache through ``kv_b_proj`` at each
step.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import NEG_INF, AttnSpec, chunked_attention

Array = jax.Array
Params = Any

__all__ = ["MLASpec", "init_mla", "mla_forward", "init_mla_cache",
           "mla_decode"]


@dataclasses.dataclass(frozen=True)
class MLASpec:
    d_model: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    q_chunk: int = 256
    kv_chunk: int = 512
    compute_dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def attn_spec(self) -> AttnSpec:
        """What ``chunked_attention`` reads: one key per query head."""
        return AttnSpec(d_model=self.d_model, num_heads=self.num_heads,
                        num_kv_heads=self.num_heads,
                        head_dim=self.qk_head_dim, causal=True,
                        q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
                        compute_dtype=self.compute_dtype)


def init_mla(key, spec: MLASpec) -> Params:
    kq, ka, kb, ko = jax.random.split(key, 4)
    d, h = spec.d_model, spec.num_heads
    return {
        "q_proj": L.init_dense(kq, d, h * spec.qk_head_dim),
        "kv_a_proj_with_mqa": L.init_dense(
            ka, d, spec.kv_lora_rank + spec.qk_rope_head_dim),
        "kv_a_layernorm": L.init_rmsnorm(spec.kv_lora_rank),
        "kv_b_proj": L.init_dense(
            kb, spec.kv_lora_rank,
            h * (spec.qk_nope_head_dim + spec.v_head_dim)),
        "o_proj": L.init_dense(ko, h * spec.v_head_dim, d),
    }


def _deinterleave(x: Array) -> Array:
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _rope(x: Array, spec: MLASpec, positions: Array) -> Array:
    cos, sin = L.rope_freqs(spec.qk_rope_head_dim, spec.rope_theta,
                            positions)
    return L.apply_rope(_deinterleave(x), cos, sin)


def _queries(p: Params, spec: MLASpec, x: Array, positions: Array) -> Array:
    b, s, _ = x.shape
    q = L.dense(p["q_proj"], x, spec.compute_dtype).reshape(
        b, s, spec.num_heads, spec.qk_head_dim)
    nope, pe = jnp.split(q, [spec.qk_nope_head_dim], axis=-1)
    return jnp.concatenate([nope, _rope(pe, spec, positions)], axis=-1)


def _latent(p: Params, spec: MLASpec, x: Array,
            positions: Array) -> tuple[Array, Array]:
    """(normed latent (B,S,R), rotated shared rope key (B,S,rope))."""
    kv = L.dense(p["kv_a_proj_with_mqa"], x, spec.compute_dtype)
    c, pe = jnp.split(kv, [spec.kv_lora_rank], axis=-1)
    c = L.rmsnorm(p["kv_a_layernorm"], c, spec.norm_eps)
    return c, _rope(pe[:, :, None, :], spec, positions)[:, :, 0]


def _keys_values(p: Params, spec: MLASpec, c: Array,
                 k_pe: Array) -> tuple[Array, Array]:
    b, s, _ = c.shape
    kv = L.dense(p["kv_b_proj"], c, spec.compute_dtype).reshape(
        b, s, spec.num_heads, spec.qk_nope_head_dim + spec.v_head_dim)
    k_nope, v = jnp.split(kv, [spec.qk_nope_head_dim], axis=-1)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :].astype(k_nope.dtype),
                            (b, s, spec.num_heads, spec.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def mla_forward(p: Params, spec: MLASpec, x: Array,
                positions: Array | None = None) -> Array:
    """Causal latent self-attention over (B, S, D)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q = _queries(p, spec, x, positions)
    k, v = _keys_values(p, spec, *_latent(p, spec, x, positions))
    out = chunked_attention(q[:, :, :, None, :], k, v, spec.attn_spec())
    return L.dense(p["o_proj"],
                   out.reshape(b, s, spec.num_heads * spec.v_head_dim),
                   spec.compute_dtype)


def init_mla_cache(spec: MLASpec, batch: int, max_seq: int,
                   dtype=None) -> Params:
    dtype = spec.compute_dtype if dtype is None else dtype
    return {"c": jnp.zeros((batch, max_seq, spec.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_seq, spec.qk_rope_head_dim),
                              dtype)}


def mla_decode(p: Params, spec: MLASpec, x: Array, cache: Params,
               pos: Array) -> tuple[Array, Params]:
    """One token per row: x (B, 1, D); ``pos`` a scalar or (B,) vector."""
    b = x.shape[0]
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    positions = pos_vec[:, None]
    q = _queries(p, spec, x, positions)                   # (B, 1, H, Dqk)
    c_new, pe_new = _latent(p, spec, x, positions)
    upd = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, axis=0))
    cache = {"c": upd(cache["c"], c_new.astype(cache["c"].dtype), pos_vec),
             "k_pe": upd(cache["k_pe"], pe_new.astype(cache["k_pe"].dtype),
                         pos_vec)}
    k, v = _keys_values(p, spec, cache["c"].astype(spec.compute_dtype),
                        cache["k_pe"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / (spec.qk_head_dim ** 0.5)
    mask = jnp.arange(cache["c"].shape[1])[None, :] <= pos_vec[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(spec.compute_dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(
        b, 1, spec.num_heads * spec.v_head_dim)
    return L.dense(p["o_proj"], out, spec.compute_dtype), cache
