"""Decoder-only transformer stack covering dense / MoE / SSM / hybrid / VLM
families, built as *segments* of scanned layers.

A :class:`Segment` is ``(kinds, count)``: a tuple of layer kinds forming one
scan body, repeated ``count`` times with stacked parameters.  This keeps the
HLO size O(#segments) regardless of depth and expresses interleaved patterns
exactly (e.g. gemma3's 5 local + 1 global per scan body; zamba2's 6 mamba2
blocks + 1 *shared* attention block whose parameters are not scanned).

Layer kinds:
  ``attn``    full-causal attention + MLP (SwiGLU or MoE)
  ``dense``   full-causal attention + SwiGLU MLP: the leading
              ``first_k_dense`` layers of a MoE model, their own segment

Attention is GQA, or latent attention (``repro.models.mla``) where the
config has ``mla``.
  ``swa``     sliding-window GQA attention + MLP
  ``mamba1``  Mamba-1 selective-scan block (no MLP, as in the original arch)
  ``mamba2``  Mamba-2 SSD block
  ``shared``  hybrid shared attention+MLP block (one param set reused)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import (AttnSpec, attn_decode, attn_forward,
                                    init_attention, init_kv_cache)

Array = jax.Array
Params = Any

__all__ = ["Segment", "build_plan", "init_lm", "forward_hidden", "lm_loss",
           "init_cache", "decode_step", "specs_for"]


Segment = tuple[tuple[str, ...], int]


def build_plan(cfg: ModelConfig) -> list[Segment]:
    n = cfg.num_layers
    if cfg.family == "ssm":
        return [(("mamba1",), n)]
    if cfg.family == "hybrid":
        period = cfg.attn_period or 6
        groups, rem = divmod(n, period)
        plan: list[Segment] = []
        if groups:
            plan.append((("mamba2",) * period + ("shared",), groups))
        if rem:
            plan.append((("mamba2",) * rem, 1))
        return plan
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        groups, rem = divmod(n, r + 1)
        plan = []
        if groups:
            plan.append((("swa",) * r + ("attn",), groups))
        if rem:
            plan.append((("swa",) * rem, 1))
        return plan
    kind = "swa" if cfg.sliding_window else "attn"
    k = cfg.first_k_dense
    return ([(("dense",), k)] if k else []) + [((kind,), n - k)]


def specs_for(cfg: ModelConfig):
    """Attention / MoE / SSM specs derived from a ModelConfig."""
    cd = jnp.dtype(cfg.compute_dtype)
    qc, kvc = cfg.attn_chunks if L.perf_opt_enabled("attn_chunks") \
        else (256, 512)
    attn = AttnSpec(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        use_rope=cfg.family != "audio", causal=True, window=None,
        q_chunk=qc, kv_chunk=kvc,
        norm_eps=cfg.norm_eps, compute_dtype=cd)
    swa = dataclasses.replace(attn, window=cfg.sliding_window or 4096)
    if cfg.mla is not None:
        attn = mla_lib.MLASpec(
            d_model=cfg.d_model, num_heads=cfg.num_heads,
            kv_lora_rank=cfg.mla.kv_lora_rank,
            qk_nope_head_dim=cfg.mla.qk_nope_head_dim,
            qk_rope_head_dim=cfg.mla.qk_rope_head_dim,
            v_head_dim=cfg.mla.v_head_dim, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, q_chunk=qc, kv_chunk=kvc,
            compute_dtype=cd)
    moe = None
    if cfg.moe is not None:
        moe = moe_lib.MoESpec(
            d_model=cfg.d_model, num_experts=cfg.moe.num_experts,
            top_k=cfg.moe.top_k, d_ff_expert=cfg.moe.d_ff_expert,
            capacity_factor=cfg.moe.capacity_factor,
            router_aux_coef=cfg.moe.router_aux_coef,
            num_shared_experts=cfg.moe.num_shared_experts,
            dropless=cfg.moe.dropless, scoring=cfg.moe.scoring,
            routed_scale=cfg.moe.routed_scale, grouped=cfg.moe.grouped,
            experts_held=cfg.moe.experts_held, compute_dtype=cd)
    m1 = m2 = None
    if cfg.ssm is not None:
        # §Perf P2b: larger scan chunks cut per-iteration boundary traffic
        # (measured: falcon train memory term 164→76 s from 128→1024).
        m1_chunk = (max(cfg.ssm.chunk, 1024)
                    if L.perf_opt_enabled("ssm_chunk") else cfg.ssm.chunk)
        if cfg.ssm.version == 1:
            m1 = ssm_lib.Mamba1Spec(
                d_model=cfg.d_model, d_state=cfg.ssm.d_state,
                d_conv=cfg.ssm.d_conv, expand=cfg.ssm.expand,
                dt_rank=cfg.ssm.dt_rank, chunk=m1_chunk,
                compute_dtype=cd)
        else:
            m2 = ssm_lib.Mamba2Spec(
                d_model=cfg.d_model, d_state=cfg.ssm.d_state,
                d_conv=cfg.ssm.d_conv, expand=cfg.ssm.expand,
                head_dim=cfg.ssm.head_dim, chunk=cfg.ssm.chunk,
                compute_dtype=cd)
    return attn, swa, moe, m1, m2


# ------------------------------------------------------------------ init

def _init_attn(key, spec) -> Params:
    if isinstance(spec, mla_lib.MLASpec):
        return mla_lib.init_mla(key, spec)
    return init_attention(key, spec)


def _attend(p: Params, spec, x: Array, positions: Array | None) -> Array:
    if isinstance(spec, mla_lib.MLASpec):
        return mla_lib.mla_forward(p, spec, x, positions)
    return attn_forward(p, spec, x, positions)


def _init_layer(key, kind: str, cfg: ModelConfig) -> Params:
    attn, swa, moe, m1, m2 = specs_for(cfg)
    k1, k2 = jax.random.split(key)
    if kind in ("attn", "swa", "shared", "dense"):
        spec = swa if kind == "swa" else attn
        p = {"ln1": L.init_rmsnorm(cfg.d_model),
             "attn": _init_attn(k1, spec),
             "ln2": L.init_rmsnorm(cfg.d_model)}
        if cfg.moe is not None and kind not in ("shared", "dense"):
            p["moe"] = moe_lib.init_moe(k2, moe)
        else:
            d_ff = cfg.d_ff or 4 * cfg.d_model
            p["mlp"] = L.init_swiglu(k2, cfg.d_model, d_ff)
        return p
    if kind == "mamba1":
        return {"ln": L.init_rmsnorm(cfg.d_model),
                "mamba": ssm_lib.init_mamba1(k1, m1)}
    if kind == "mamba2":
        return {"ln": L.init_rmsnorm(cfg.d_model),
                "mamba": ssm_lib.init_mamba2(k1, m2)}
    raise ValueError(kind)


def init_lm(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    params: Params = {"embed": L.init_embedding(keys[0], cfg.vocab_size,
                                                cfg.d_model)}
    plan = build_plan(cfg)
    seg_params = []
    for si, (kinds, count) in enumerate(plan):
        seg: Params = {}
        for pi, kind in enumerate(kinds):
            name = f"{pi}_{kind}"
            if kind == "shared":
                continue    # shared params live at top level
            kseed = jax.random.fold_in(keys[1], si * 64 + pi)
            init_one = functools.partial(_init_layer, kind=kind, cfg=cfg)
            seg[name] = jax.vmap(lambda k: init_one(k))(
                jax.random.split(kseed, count))
        seg_params.append(seg)
    params["segments"] = seg_params
    if any("shared" in kinds for kinds, _ in plan):
        params["shared_block"] = _init_layer(keys[2], "shared", cfg)
    params["final_norm"] = L.init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(keys[3], cfg.d_model,
                                         cfg.vocab_size, scale=0.02)
    return params


# ------------------------------------------------------------------ forward

def _apply_layer(p: Params, kind: str, cfg: ModelConfig, x: Array,
                 positions: Array | None, aux: tuple) -> tuple[Array, tuple]:
    """One layer; ``aux`` is (auxiliary loss, routed expert rows), summed
    over the layers so far."""
    attn, swa, moe, m1, m2 = specs_for(cfg)
    if kind in ("attn", "swa", "shared", "dense"):
        spec = swa if kind == "swa" else attn
        x = x + _attend(p["attn"], spec, L.rmsnorm(p["ln1"], x,
                                                   cfg.norm_eps),
                        positions)
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            y, a, n = moe_lib.moe_forward(p["moe"], moe, h)
            aux = (aux[0] + a, aux[1] + n)
        else:
            y = L.swiglu(p["mlp"], h, spec.compute_dtype)
        return x + y, aux
    if kind == "mamba1":
        return x + ssm_lib.mamba1_forward(p["mamba"],
                                          m1, L.rmsnorm(p["ln"], x,
                                                        cfg.norm_eps)), aux
    if kind == "mamba2":
        return x + ssm_lib.mamba2_forward(p["mamba"],
                                          m2, L.rmsnorm(p["ln"], x,
                                                        cfg.norm_eps)), aux
    raise ValueError(kind)


def forward_hidden(params: Params, cfg: ModelConfig, x: Array,
                   positions: Array | None = None, *, remat: bool = True
                   ) -> tuple[Array, tuple]:
    """Embedded inputs (B,S,D) -> final hidden (B,S,D), (aux loss, routed
    (token, expert) pairs the MoE layers computed, int32)."""
    plan = build_plan(cfg)
    aux0 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))

    def seg_scan(x, aux, seg_p, kinds):
        def body(carry, layer_p):
            h, a = carry
            for pi, kind in enumerate(kinds):
                name = f"{pi}_{kind}"
                if kind == "shared":
                    h, a = _apply_layer(params["shared_block"], "shared",
                                        cfg, h, positions, a)
                else:
                    h, a = _apply_layer(layer_p[name], kind, cfg, h,
                                        positions, a)
            return (h, a), None

        body_fn = jax.checkpoint(body) if remat else body
        (x, aux), _ = jax.lax.scan(body_fn, (x, aux), seg_p)
        return x, aux

    aux = aux0
    for seg_p, (kinds, _count) in zip(params["segments"], plan):
        x, aux = seg_scan(x, aux, seg_p, kinds)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def _embed_inputs(params: Params, cfg: ModelConfig, batch: dict) -> Array:
    cd = jnp.dtype(cfg.compute_dtype)
    x = L.embed(params["embed"], batch["tokens"], cd)
    if cfg.frontend == "vision" and "patch_embeddings" in batch:
        # VLM: prefix the (stub-encoded, pre-projected) patch embeddings.
        x = jnp.concatenate([batch["patch_embeddings"].astype(cd), x], axis=1)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model, cd) ** 0.5
    return x


def lm_loss(params: Params, cfg: ModelConfig, batch: dict, *,
            remat: bool = True, with_accuracy: bool = False,
            with_rows: bool = False):
    """Next-token CE loss.  batch: tokens (B,S), labels (B,S) [, mask,
    patch_embeddings].  ``with_accuracy`` appends the next-token accuracy,
    ``with_rows`` the routed (token, expert) pairs the MoE layers
    computed: ``(loss, accuracy, rows)`` with both."""
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    hidden, (aux, rows) = forward_hidden(params, cfg, x, positions,
                                         remat=remat)
    n_text = batch["tokens"].shape[1]
    hidden = hidden[:, -n_text:]    # VLM: loss only over text positions
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    ce = L.chunked_cross_entropy(head, hidden, batch["labels"],
                                 tie=cfg.tie_embeddings,
                                 compute_dtype=jnp.dtype(cfg.compute_dtype),
                                 mask=batch.get("mask"),
                                 with_accuracy=with_accuracy)
    out = ((ce[0] + aux, ce[1]) if with_accuracy else (ce + aux,)) \
        + ((rows,) if with_rows else ())
    return out if len(out) > 1 else out[0]


# ------------------------------------------------------------------ decode

def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Params:
    attn, swa, moe, m1, m2 = specs_for(cfg)
    plan = build_plan(cfg)
    segs = []
    for kinds, count in plan:
        seg: Params = {}
        for pi, kind in enumerate(kinds):
            name = f"{pi}_{kind}"
            if isinstance(attn, mla_lib.MLASpec) and kind in ("attn",
                                                             "dense"):
                one = mla_lib.init_mla_cache(attn, batch, max_seq)
            elif kind in ("attn", "swa", "dense"):
                spec = swa if kind == "swa" else attn
                # A sliding-window layer only ever reads the last `window`
                # entries — allocate a ring of that size, rounded up to a
                # multiple of 256 so the ring is seq-shardable over up to
                # (data × model) = 256 devices.
                if kind == "swa":
                    length = min(-(-(spec.window + 1) // 256) * 256, max_seq)
                else:
                    length = max_seq
                one = init_kv_cache(spec, batch, length)
            elif kind == "shared":
                one = init_kv_cache(attn, batch, max_seq)
            elif kind == "mamba1":
                one = ssm_lib.init_mamba1_cache(m1, batch)
            elif kind == "mamba2":
                one = ssm_lib.init_mamba2_cache(m2, batch)
            else:
                raise ValueError(kind)
            seg[name] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (count,) + a.shape).copy(), one)
        segs.append(seg)
    return {"segments": segs}


def decode_step(params: Params, cfg: ModelConfig, tokens: Array,
                cache: Params, pos: Array) -> tuple[Array, Params]:
    """One decode step.  tokens: (B, 1) int32; pos: scalar current length.

    Returns (logits (B, 1, V), new cache).
    """
    attn, swa, moe, m1, m2 = specs_for(cfg)
    cd = jnp.dtype(cfg.compute_dtype)
    x = L.embed(params["embed"], tokens, cd)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model, cd) ** 0.5
    plan = build_plan(cfg)
    new_segs = []
    for seg_p, seg_c, (kinds, _count) in zip(params["segments"],
                                             cache["segments"], plan):
        def body(carry, xs):
            h = carry
            layer_p, layer_c = xs
            new_c = {}
            for pi, kind in enumerate(kinds):
                name = f"{pi}_{kind}"
                if kind in ("attn", "swa", "shared", "dense"):
                    spec = swa if kind == "swa" else attn
                    p = (params["shared_block"] if kind == "shared"
                         else layer_p[name])
                    c = layer_c[name]
                    hn = L.rmsnorm(p["ln1"], h, cfg.norm_eps)
                    if isinstance(spec, mla_lib.MLASpec):
                        y, c2 = mla_lib.mla_decode(p["attn"], spec, hn, c,
                                                   pos)
                    else:
                        # SWA caches are rings of length min(window+1,
                        # max_seq); the ring math degenerates to linear
                        # while pos < length.
                        y, c2 = attn_decode(p["attn"], spec, hn, c, pos,
                                            ring=(kind == "swa"))
                    h = h + y
                    hh = L.rmsnorm(p["ln2"], h, cfg.norm_eps)
                    if "moe" in p:
                        y2, _, _ = moe_lib.moe_forward(p["moe"], moe, hh)
                    else:
                        y2 = L.swiglu(p["mlp"], hh, cd)
                    h = h + y2
                    new_c[name] = c2
                elif kind == "mamba1":
                    y, c2 = ssm_lib.mamba1_decode(
                        layer_p[name]["mamba"], m1,
                        L.rmsnorm(layer_p[name]["ln"], h, cfg.norm_eps),
                        layer_c[name])
                    h = h + y
                    new_c[name] = c2
                elif kind == "mamba2":
                    y, c2 = ssm_lib.mamba2_decode(
                        layer_p[name]["mamba"], m2,
                        L.rmsnorm(layer_p[name]["ln"], h, cfg.norm_eps),
                        layer_c[name])
                    h = h + y
                    new_c[name] = c2
            return h, new_c

        x, new_c = jax.lax.scan(body, x, (seg_p, seg_c))
        new_segs.append(new_c)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.unembed_logits(params["embed"], x, cd)
    else:
        logits = L.dense(params["lm_head"], x, cd)
    return logits.astype(jnp.float32), {"segments": new_segs}
