"""Plain reference of ``moonlight_16b_a3b``: Moonlight-16B-A3B (DeepSeek-V3
layers) cut to one chip's share, in straightforward ``jax.numpy``,
imported from nothing of the program.

Over token ids: an embedding; ``first_k_dense_replace`` layers with a dense
SwiGLU MLP, then MoE layers; each layer is pre-norm multi-head latent
attention and its MLP, with RMS norms (``rms_norm_eps``) and residuals;
then a final norm and the untied head over the vocabulary slice.  The loss
is the mean next-token cross-entropy over each row's ``seq`` positions (the
class ``y`` is not used).

* Attention: ``q_proj`` gives each head ``qk_nope_head_dim`` plus
  ``qk_rope_head_dim``; ``kv_a_proj_with_mqa`` a latent of ``kv_lora_rank``
  (normed) and one rope key for all heads; ``kv_b_proj`` each head's key and
  value from the latent.  Rope (``rope_theta``) turns interleaved pairs
  ``(2i, 2i + 1)`` by ``pos * theta^(-2i / rope)``.  Causal softmax at scale
  ``1/sqrt(nope + rope)``.  Each of the four projections is ``W`` plus the
  LoRA term ``(h a) b``.
* MoE: sigmoid scores of the router (``experts_routed`` outputs, float32
  at ``HIGHEST`` whatever the precision of the rest), the top
  ``num_experts_per_tok`` of score plus ``e_score_correction_bias``, weights
  the unbiased scores normalised over them times ``routed_scaling_factor``.
  The chip holds the first ``n_routed_experts`` experts: each held expert
  runs on every token and its output counts with the weight the token gave
  it (0 where it was not chosen); the shared experts run on every token.
  What experts held elsewhere would add is left out, as in the program.

Departures from the published model: the sequence-wise auxiliary loss
(``seq_aux``) is left out, the router being frozen; the layers, experts
and vocabulary are one chip's share (the configuration's ``reduced``).

``frozen(conf, key)`` builds the base in the program's layout (the
harness hands it to both sides); ``init(conf, key)`` the adapters, the
tree the fleet trains.  ``compute_dtype`` float32 runs every product at
``HIGHEST``; a lower type rounds the operands of every product but the
router's to it, with float32 accumulation (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PROJ = ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
B_SCALE = 0.01                  # of the LoRA factor b, N(0,1) * B_SCALE
BIAS_SCALE = 0.1                # of e_score_correction_bias


def _proj_shapes(conf: dict) -> dict:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    r, v = conf["kv_lora_rank"], conf["v_head_dim"]
    return {"q_proj": (d, h * (nope + rope)),
            "kv_a_proj_with_mqa": (d, r + rope),
            "kv_b_proj": (r, h * (nope + v)),
            "o_proj": (h * v, d)}


def _segments(conf: dict) -> list:
    """(name, layers) of the two stacks: dense first, then MoE."""
    k = conf["first_k_dense_replace"]
    return [("0_dense", k), ("0_attn", conf["num_hidden_layers"] - k)]


def frozen(conf: dict, key) -> dict:
    """The base, bfloat16 but for the float32 selection bias."""
    bf = jnp.bfloat16
    d, ff, fe = (conf["hidden_size"], conf["intermediate_size"],
                 conf["moe_intermediate_size"])
    held, routed = conf["n_routed_experts"], conf["experts_routed"]
    shared = conf["n_shared_experts"] * fe
    keys = iter(jax.random.split(key, 64))

    def lin(n, shape):
        return (jax.random.normal(next(keys), (n,) + shape, jnp.float32)
                / jnp.sqrt(shape[-2])).astype(bf)

    def ones(n, width):
        return jnp.ones((n, width), bf)

    def block(n, moe):
        attn = {p: {"w": lin(n, s)} for p, s in _proj_shapes(conf).items()}
        attn["kv_a_layernorm"] = {"scale": ones(n, conf["kv_lora_rank"])}
        out = {"ln1": {"scale": ones(n, d)}, "attn": attn,
               "ln2": {"scale": ones(n, d)}}
        if not moe:
            out["mlp"] = {"w_gate": {"w": lin(n, (d, ff))},
                          "w_up": {"w": lin(n, (d, ff))},
                          "w_down": {"w": lin(n, (ff, d))}}
            return out
        out["moe"] = {
            "router": {"w": lin(n, (d, routed))},
            "router_bias": jax.random.normal(next(keys), (n, routed),
                                             jnp.float32) * BIAS_SCALE,
            "w_gate": lin(n, (held, d, fe)), "w_up": lin(n, (held, d, fe)),
            "w_down": lin(n, (held, fe, d)),
            "shared": {"w_gate": {"w": lin(n, (d, shared))},
                       "w_up": {"w": lin(n, (d, shared))},
                       "w_down": {"w": lin(n, (shared, d))}}}
        return out

    segs = [{name: block(n, name != "0_dense")}
            for name, n in _segments(conf)]
    v = conf["vocab_size"]
    return {"embed": {"table": (jax.random.normal(next(keys), (v, d),
                                                  jnp.float32)
                                * 0.02).astype(bf)},
            "segments": segs,
            "final_norm": {"scale": jnp.ones((d,), bf)},
            "lm_head": {"w": lin(1, (d, v))[0]}}


def init(conf: dict, key) -> dict:
    """The adapters, both factors drawn: a (fan_in, rank), b (rank,
    fan_out), stacked over each segment's layers."""
    r = conf["lora_rank"]
    segs = []
    for si, (name, n) in enumerate(_segments(conf)):
        ks = jax.random.split(jax.random.fold_in(key, si), 2 * len(PROJ))
        attn = {}
        for j, (p, (a, b)) in enumerate(_proj_shapes(conf).items()):
            attn[p] = {
                "lora_a": jax.random.normal(ks[2 * j], (n, a, r),
                                            jnp.float32) / jnp.sqrt(a),
                "lora_b": jax.random.normal(ks[2 * j + 1], (n, r, b),
                                            jnp.float32) * B_SCALE}
        segs.append({name: {"attn": attn}})
    return {"segments": segs}


def _mm(spec, a, b, dtype):
    prec = HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=prec, preferred_element_type=jnp.float32)


def _rms(h, scale, eps):
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return h * scale.astype(jnp.float32)


def _proj(h, base, lora, dtype):
    out = _mm("...i,io->...o", h, base["w"], dtype)
    if lora is not None:
        low = _mm("...i,ir->...r", h, lora["lora_a"], dtype)
        out = out + _mm("...r,ro->...o", low, lora["lora_b"], dtype)
    return out


def _rope(x, pos, theta):
    """Interleaved pairs (2i, 2i + 1) of the last axis, turned by
    ``pos * theta^(-2i / width)``; x (B, S, H, width)."""
    w = x.shape[-1]
    freq = theta ** (-jnp.arange(0, w, 2, dtype=jnp.float32) / w)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]      # (S, w/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _attention(h, bl, lo, conf, dtype):
    b, s, _ = h.shape
    nh = conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    r, dv = conf["kv_lora_rank"], conf["v_head_dim"]
    eps, theta = conf["rms_norm_eps"], float(conf["rope_theta"])
    pos = jnp.arange(s)
    q = _proj(h, bl["q_proj"], lo["q_proj"], dtype).reshape(
        b, s, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)],
                        axis=-1)
    kva = _proj(h, bl["kv_a_proj_with_mqa"], lo["kv_a_proj_with_mqa"], dtype)
    c = _rms(kva[..., :r], bl["kv_a_layernorm"]["scale"], eps)
    k_pe = _rope(kva[..., None, r:], pos, theta)                # (B,S,1,rope)
    kv = _proj(c, bl["kv_b_proj"], lo["kv_b_proj"], dtype).reshape(
        b, s, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, nh, rope))], axis=-1)
    v = kv[..., nope:]
    att = _mm("bqhd,bkhd->bhqk", q, k, dtype) / jnp.sqrt(nope + rope)
    mask = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(mask[None, None], att, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", att, v, dtype).reshape(b, s, nh * dv)
    return _proj(o, bl["o_proj"], lo["o_proj"], dtype)


def _swiglu(h, p, dtype):
    g = _mm("...i,io->...o", h, p["w_gate"]["w"], dtype)
    u = _mm("...i,io->...o", h, p["w_up"]["w"], dtype)
    return _mm("...i,io->...o", jax.nn.silu(g) * u, p["w_down"]["w"], dtype)


def routing(h, moe, conf):
    """(T, D) -> top experts (T, k) and their weights (T, k)."""
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32),
                        moe["router"]["w"].astype(jnp.float32),
                        precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(scores + moe["router_bias"],
                           conf["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top, w * conf["routed_scaling_factor"]


def moe_layer(h, moe, conf, dtype):
    """The held experts' and the shared experts' part of an MoE layer's
    output; h (T, D)."""
    top, w = routing(h, moe, conf)
    held = moe["w_gate"].shape[0]
    # (T, held): the weight each token gave each held expert, 0 if unchosen
    gate = jnp.sum(jnp.where(top[:, :, None] == jnp.arange(held), w[..., None],
                             0.0), axis=1)
    g = _mm("td,edf->tef", h, moe["w_gate"], dtype)
    u = _mm("td,edf->tef", h, moe["w_up"], dtype)
    eo = _mm("tef,efd->ted", jax.nn.silu(g) * u, moe["w_down"], dtype)
    return jnp.einsum("ted,te->td", eo, gate, precision=HIGHEST) \
        + _swiglu(h, moe["shared"], dtype)


def logits(q, tok, conf: dict, dtype, fz):
    b, s = tok.shape
    eps = conf["rms_norm_eps"]
    h = fz["embed"]["table"].astype(jnp.float32)[tok]
    for seg, lseg in zip(fz["segments"], q["segments"]):
        (name, stack), = seg.items()
        n = jax.tree.leaves(stack)[0].shape[0]
        for i in range(n):
            bl = jax.tree.map(lambda a: a[i], stack)
            lo = jax.tree.map(lambda a: a[i], lseg[name]["attn"])
            h = h + _attention(_rms(h, bl["ln1"]["scale"], eps), bl["attn"],
                               lo, conf, dtype)
            hn = _rms(h, bl["ln2"]["scale"], eps)
            if "moe" in bl:
                h = h + moe_layer(hn.reshape(b * s, -1), bl["moe"], conf,
                                  dtype).reshape(h.shape)
            else:
                h = h + _swiglu(hn, bl["mlp"], dtype)
    h = _rms(h, fz["final_norm"]["scale"], eps)
    return _mm("bsd,dv->bsv", h, fz["lm_head"]["w"], dtype)


def loss(q, x, y, conf: dict, dtype=jnp.float32, fz=None):
    tok = x.astype(jnp.int32)
    z = logits(q, tok[:, :-1], conf, dtype, fz)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, tok[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def train_flops_per_row(conf: dict, mix: dict) -> int:
    """Model FLOPs of one training row: the frozen products forward and
    to their inputs (two passes), the adapters and the causal attention
    pairs forward, to their inputs and to their other operand (three), the
    routed experts at ``num_experts_per_tok`` times the held share of the
    router's experts a token; nothing recomputed."""
    s = conf["data"]["seq"]
    d, ff, fe = (conf["hidden_size"], conf["intermediate_size"],
                 conf["moe_intermediate_size"])
    nh = conf["num_attention_heads"]
    nope, rope, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                      conf["v_head_dim"])
    layers, dense = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    per_tok = (conf["num_experts_per_tok"] * conf["n_routed_experts"]
               / conf["experts_routed"])
    mla = sum(a * b for a, b in _proj_shapes(conf).values())
    moe = (3 * d * fe * (conf["n_shared_experts"] + per_tok)
           + d * conf["experts_routed"])
    frozen_macs = (layers * mla + dense * 3 * d * ff
                   + (layers - dense) * moe + d * conf["vocab_size"])
    lora_macs = layers * conf["lora_rank"] * sum(
        a + b for a, b in _proj_shapes(conf).values())
    attn_macs = layers * nh * (nope + rope + dv) * (s + 1) / 2
    return int(s * 2 * (2 * frozen_macs + 3 * (lora_macs + attn_macs)))
