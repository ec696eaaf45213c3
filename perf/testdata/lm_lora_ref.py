"""Plain reference of ``lm_lora``: the program's ``lm`` task in
straightforward ``jax.numpy``, imported from nothing of the program.

A pre-norm transformer over token ids: an embedding, ``layers`` blocks of
causal multi-head attention and a ReLU MLP, each projection ``W`` plus a
LoRA term ``(h a) b``, RMS norms (eps 1e-6), and the tied embedding as the
head; the loss is the mean next-token cross-entropy over each row's
``seq`` positions (the class ``y`` is not used).  ``frozen(conf, key)``
builds the base, which never trains; ``init(conf, key)`` the adapter tree,
the one the fleet trains.  ``compute_dtype`` float32 runs every product at
``HIGHEST`` precision; a lower type rounds the operands of every product
to it, with float32 accumulation (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PROJ = ("wq", "wk", "wv", "wo", "w1", "w2")
B_SCALE = 0.1                   # of the LoRA factor b, N(0,1) * B_SCALE


def _shapes(conf: dict) -> dict:
    d, ff = conf["width"], conf["ff"]
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "w1": (d, ff), "w2": (ff, d)}


def frozen(conf: dict, key) -> dict:
    """The base: embedding N(0,1)*0.02, projections N(0,1)/sqrt(fan_in)."""
    ke, kb = jax.random.split(key)
    shapes = _shapes(conf)
    layers = []
    for i in range(conf["layers"]):
        ks = jax.random.split(jax.random.fold_in(kb, i), len(PROJ))
        layers.append({n: jax.random.normal(k, shapes[n], jnp.float32)
                       / jnp.sqrt(shapes[n][0]) for k, n in zip(ks, PROJ)})
    emb = jax.random.normal(ke, (conf["vocab"], conf["width"]),
                            jnp.float32) * 0.02
    return {"embed": emb, "layers": layers}


def init(conf: dict, key) -> list:
    """The adapters, both factors drawn: a (fan_in, rank), b (rank,
    fan_out)."""
    shapes, r = _shapes(conf), conf["rank"]
    out = []
    for i in range(conf["layers"]):
        ks = jax.random.split(jax.random.fold_in(key, i), 2 * len(PROJ))
        layer = {}
        for j, n in enumerate(PROJ):
            a, b = shapes[n]
            layer[n] = {
                "a": jax.random.normal(ks[2 * j], (a, r), jnp.float32)
                / jnp.sqrt(a),
                "b": jax.random.normal(ks[2 * j + 1], (r, b), jnp.float32)
                * B_SCALE}
        out.append(layer)
    return out


def _mm(spec, a, b, dtype):
    prec = HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=prec, preferred_element_type=jnp.float32)


def _rms(h):
    return h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + 1e-6)


def _proj(h, base, lora, dtype):
    low = _mm("...i,ir->...r", h, lora["a"], dtype)
    return (_mm("...i,io->...o", h, base, dtype)
            + _mm("...r,ro->...o", low, lora["b"], dtype))


def logits(q, tok, conf: dict, dtype, fz):
    b, s = tok.shape
    nh = conf["heads"]
    hd = conf["width"] // nh
    h = fz["embed"][tok]
    mask = jnp.tril(jnp.ones((s, s), bool))
    for bl, lo in zip(fz["layers"], q):
        hn = _rms(h)
        qh, kh, vh = (_proj(hn, bl[n], lo[n], dtype).reshape(b, s, nh, hd)
                      for n in ("wq", "wk", "wv"))
        att = _mm("bqhd,bkhd->bhqk", qh, kh, dtype) / jnp.sqrt(hd)
        att = jax.nn.softmax(jnp.where(mask[None, None], att, -jnp.inf),
                             axis=-1)
        o = _mm("bhqk,bkhd->bqhd", att, vh, dtype).reshape(b, s, -1)
        h = h + _proj(o, bl["wo"], lo["wo"], dtype)
        mid = jnp.maximum(_proj(_rms(h), bl["w1"], lo["w1"], dtype), 0)
        h = h + _proj(mid, bl["w2"], lo["w2"], dtype)
    return _mm("bsd,vd->bsv", _rms(h), fz["embed"], dtype)


def loss(q, x, y, conf: dict, dtype=jnp.float32, fz=None):
    tok = x.astype(jnp.int32)
    z = logits(q, tok[:, :-1], conf, dtype, fz)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, tok[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def train_flops_per_row(conf: dict, mix: dict) -> int:
    """Forward, backward to the inputs (as long again), and the adapters'
    weight gradients; the frozen base has none."""
    s = conf["data"]["seq"]
    d, ff, v, r, nl = (conf["width"], conf["ff"], conf["vocab"],
                       conf["rank"], conf["layers"])
    base = 4 * d * d + 2 * d * ff
    lora = r * sum(a + b for a, b in _shapes(conf).values())
    attn = 2 * s * d
    forward = 2 * s * (nl * (base + lora + attn) + d * v)
    return 2 * forward + 2 * s * nl * lora
