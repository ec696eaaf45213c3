"""Shared neural building blocks (pure functional JAX).

Parameters are plain nested dicts of ``jax.Array``.  Every ``init_*`` takes a
PRNG key and returns the param subtree; every ``apply``-style function takes
``(params, inputs)``.  Compute runs in ``compute_dtype`` (bf16 by default)
with fp32 master params and fp32 norm/softmax accumulation.
"""
from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# Experimental opts that must be requested EXPLICITLY (never part of "all")
_OPT_IN = frozenset({"embed_dshard"})


def perf_opt_enabled(name: str) -> bool:
    """Beyond-paper performance optimizations (§Perf) are individually
    toggleable so the paper-faithful baseline stays reproducible:
    ``REPRO_PERF_OPTS=all`` (default) | ``none`` | comma-list of
    {ce_seqchunk, ce_mask, ssm_fuse, ssm_chunk, attn_chunks, grad_accum,
    wire_bf16, params_only_diffusion}.  Opt-in extras ({embed_dshard}) are
    enabled only when listed explicitly (``all,embed_dshard`` works)."""
    tokens = os.environ.get("REPRO_PERF_OPTS", "all").split(",")
    if name in _OPT_IN:
        return name in tokens
    if "all" in tokens:
        return True
    if tokens == ["none"]:
        return False
    return name in tokens

Array = jax.Array
Params = Any

__all__ = [
    "init_dense", "dense", "init_rmsnorm", "rmsnorm", "init_layernorm",
    "layernorm", "init_embedding", "embed", "unembed_logits", "rope_freqs",
    "apply_rope", "init_swiglu", "swiglu", "chunked_cross_entropy",
    "sinusoidal_positions", "silu", "count_params",
]


def silu(x: Array) -> Array:
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------- dense

def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32,
               scale: float | None = None) -> Params:
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}


def dense(p: Params, x: Array, compute_dtype=jnp.bfloat16) -> Array:
    """``x @ w``, plus the low-rank term ``(x @ lora_a) @ lora_b`` where the
    projection carries LoRA factors (``repro.fl.adapters.lora_merge``)."""
    x = x.astype(compute_dtype)
    y = jnp.einsum("...i,io->...o", x, p["w"].astype(compute_dtype))
    if "lora_a" in p:
        low = jnp.einsum("...i,ir->...r", x, p["lora_a"].astype(compute_dtype))
        y = y + jnp.einsum("...r,ro->...o", low,
                           p["lora_b"].astype(compute_dtype))
    return y


# ---------------------------------------------------------------- norms

def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(dtype)


def init_layernorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(dtype)


# ---------------------------------------------------------------- embedding

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed(p: Params, tokens: Array, compute_dtype=jnp.bfloat16) -> Array:
    table = p["table"]
    if perf_opt_enabled("embed_dshard"):
        # §Perf bonus (opt-in): the lookup against a (vocab×d)-sharded table
        # lowers to masked-gather + a full-token-stream all-reduce (≈1 GB ×
        # remat on 152k-vocab archs).  Resharding the table to d-only first
        # (one cheap all-to-all of the 38 MB/device table) makes the gather
        # local; the d-sharded activations flow into the TP layers natively.
        try:
            from jax.sharding import PartitionSpec as P
            table = jax.lax.with_sharding_constraint(table, P(None, "model"))
        except Exception:
            pass   # no mesh context (CPU unit tests): keep as-is
    return table.astype(compute_dtype)[tokens]


def unembed_logits(p: Params, x: Array, compute_dtype=jnp.bfloat16) -> Array:
    """Tied-embedding readout: x @ tableᵀ."""
    t = p["table"].astype(compute_dtype)
    return jnp.einsum("...d,vd->...v", x.astype(compute_dtype), t)


# ---------------------------------------------------------------- RoPE

@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _rope_table(head_dim: int, theta: float, positions: Array):
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def rope_freqs(head_dim: int, theta: float, positions: Array):
    return _rope_table(head_dim, float(theta), positions)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (..., S, H, Dh); cos/sin: (..., S, Dh/2) broadcast over heads."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)


def sinusoidal_positions(seq: int, d: int) -> Array:
    pos = np.arange(seq)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, jnp.float32)


# ---------------------------------------------------------------- SwiGLU

def init_swiglu(key, d: int, d_ff: int, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(k1, d, d_ff, dtype),
        "w_up": init_dense(k2, d, d_ff, dtype),
        "w_down": init_dense(k3, d_ff, d, dtype),
    }


def swiglu(p: Params, x: Array, compute_dtype=jnp.bfloat16) -> Array:
    g = dense(p["w_gate"], x, compute_dtype)
    u = dense(p["w_up"], x, compute_dtype)
    return dense(p["w_down"], silu(g) * u, compute_dtype)


# ---------------------------------------------------------------- loss

def chunked_cross_entropy(emb_or_head: Params, hidden: Array, labels: Array,
                          *, tie: bool, chunk: int = 512,
                          compute_dtype=jnp.bfloat16,
                          mask: Array | None = None,
                          with_accuracy: bool = False):
    """Mean next-token cross-entropy without materializing (B, S, V) logits.

    ``hidden``: (B, S, D); ``labels``: (B, S) int32.  ``with_accuracy``
    returns ``(loss, accuracy)``, the accuracy the share of tokens whose
    gold logit is the largest.

    §Perf P1: the scan runs over SEQUENCE chunks with the batch dimension
    intact.  Flattening (B·S) into the scan axis — the obvious layout —
    destroys the batch sharding: under SPMD every device must run every
    chunk of the *global* token stream, so XLA all-gathers the whole hidden
    tensor and each data-parallel rank redundantly computes all other
    ranks' logits (measured: +8.6 GB all-gather and ~16× duplicated CE
    FLOPs per device on the 16×16 mesh).  Chunking over S keeps the chunk
    slice local to each batch shard.
    """
    b, s, d = hidden.shape
    m = (jnp.ones((b, s), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    if perf_opt_enabled("ce_seqchunk"):
        chunk = min(chunk, s)
        pad = (-s) % chunk
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            m = jnp.pad(m, ((0, 0), (0, pad)))
        nchunks = hidden.shape[1] // chunk
        # scan xs carry leading chunk axis; batch stays axis 1 (sharded)
        hs = jnp.moveaxis(hidden.reshape(b, nchunks, chunk, d), 1, 0)
        ys = jnp.moveaxis(labels.reshape(b, nchunks, chunk), 1, 0)
        ms = jnp.moveaxis(m.reshape(b, nchunks, chunk), 1, 0)
    else:
        # baseline layout: flatten (B·S) into the scan axis.  Kept for the
        # §Perf A/B — under SPMD this replicates CE compute across the
        # data axis (see the P1 log).
        n = b * s
        flat_h = hidden.reshape(n, d)
        flat_y = labels.reshape(n)
        flat_m = m.reshape(n)
        pad = (-n) % chunk
        if pad:
            flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
            flat_y = jnp.pad(flat_y, (0, pad))
            flat_m = jnp.pad(flat_m, (0, pad))
        nchunks = flat_h.shape[0] // chunk
        hs = flat_h.reshape(nchunks, 1, chunk, d)
        ys = flat_y.reshape(nchunks, 1, chunk)
        ms = flat_m.reshape(nchunks, 1, chunk)

    if tie:
        w = emb_or_head["table"].astype(compute_dtype)      # (V, D)
        proj = lambda h: jnp.einsum("btd,vd->btv", h, w)
    else:
        w = emb_or_head["w"].astype(compute_dtype)          # (D, V)
        proj = lambda h: jnp.einsum("btd,dv->btv", h, w)

    # Rematerialized per chunk: the backward pass recomputes each logits
    # block instead of saving all of them (tens of GB at LM scale).
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, xs):
        h, y, msk = xs
        logits = proj(h.astype(compute_dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        if perf_opt_enabled("ce_mask"):
            # Gold-logit extraction via mask+reduce, NOT take_along_axis:
            # with vocab-sharded logits a gather forces collectives; the
            # masked reduce lowers to a local select + tiny all-reduce.
            vocab_pos = jnp.arange(logits.shape[-1])
            gold = jnp.sum(jnp.where(y[..., None] == vocab_pos, logits,
                                     0.0), axis=-1)
        else:
            gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        loss_sum, cnt, hits = carry
        if with_accuracy:
            hits = hits + jnp.sum(
                (gold >= jnp.max(logits, axis=-1)) * msk)
        return (loss_sum + jnp.sum((logz - gold) * msk),
                cnt + jnp.sum(msk), hits), None

    (loss_sum, cnt, hits), _ = jax.lax.scan(body, (0.0, 0.0, 0.0),
                                            (hs, ys, ms))
    loss = loss_sum / jnp.maximum(cnt, 1.0)
    if with_accuracy:
        return loss, hits / jnp.maximum(cnt, 1.0)
    return loss


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
