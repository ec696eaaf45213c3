"""Prefill ≡ decode consistency, chunked-attention vs naive, chunked-CE vs
dense CE, MoE routing invariants — the model-zoo correctness core."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models import layers as L
from repro.models import transformer as tf
from repro.models.attention import AttnSpec, chunked_attention
from repro.models.moe import MoESpec, init_moe, moe_forward

ARCHS = ["smollm_360m", "qwen3_0_6b", "gemma3_4b", "mixtral_8x22b",
         "falcon_mamba_7b", "zamba2_2_7b", "qwen3_moe_235b_a22b",
         "moonshot_v1_16b_a3b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_equals_decode(arch):
    cfg = dataclasses.replace(get_smoke_config(arch),
                              compute_dtype="float32")
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    b, s = 2, 20
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    x = tf._embed_inputs(params, cfg, {"tokens": toks})
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    hid, _ = tf.forward_hidden(params, cfg, x, pos, remat=False)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    want = (L.unembed_logits(head, hid, jnp.float32) if cfg.tie_embeddings
            else L.dense(head, hid, jnp.float32))
    cache = model.init_cache(params, b, s)
    outs = []
    for t in range(s):
        lg, cache = model.decode_step(params, toks[:, t:t + 1], cache,
                                      jnp.int32(t))
        outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


def test_chunked_attention_matches_naive():
    key = jax.random.PRNGKey(0)
    b, s, kh, g, d = 2, 100, 2, 3, 32
    q = jax.random.normal(key, (b, s, kh, g, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kh, d))
    spec = AttnSpec(d_model=d * kh * g, num_heads=kh * g, num_kv_heads=kh,
                    head_dim=d, q_chunk=32, kv_chunk=32,
                    compute_dtype=jnp.float32)
    out = chunked_attention(q, k, v, spec)
    scale = 1 / np.sqrt(d)
    s_ = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    s_ = jnp.where(mask[None, None, None], s_, -jnp.inf)
    p = jax.nn.softmax(s_, axis=-1)
    want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(b, s, kh * g, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_sliding_window_attention_masks_correctly():
    key = jax.random.PRNGKey(0)
    b, s, kh, g, d, w = 1, 96, 1, 1, 16, 24
    q = jax.random.normal(key, (b, s, kh, g, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kh, d))
    spec = AttnSpec(d_model=16, num_heads=1, num_kv_heads=1, head_dim=16,
                    window=w, q_chunk=32, kv_chunk=32,
                    compute_dtype=jnp.float32)
    out = chunked_attention(q, k, v, spec)
    scale = 1 / np.sqrt(d)
    s_ = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    mask = (kp <= qp) & (kp > qp - w)
    s_ = jnp.where(mask[None, None, None], s_, -jnp.inf)
    p = jax.nn.softmax(s_, axis=-1)
    want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(b, s, 1, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_chunked_cross_entropy_matches_dense():
    key = jax.random.PRNGKey(0)
    b, s, d, v = 2, 17, 8, 50
    hid = jax.random.normal(key, (b, s, d), jnp.float32)
    emb = {"table": jax.random.normal(jax.random.PRNGKey(1), (v, d))}
    y = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)
    got = L.chunked_cross_entropy(emb, hid, y, tie=True, chunk=5,
                                  compute_dtype=jnp.float32)
    logits = jnp.einsum("bsd,vd->bsv", hid, emb["table"])
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    want = jnp.mean(logz - gold)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_chunked_cross_entropy_respects_mask():
    key = jax.random.PRNGKey(0)
    b, s, d, v = 1, 8, 4, 11
    hid = jax.random.normal(key, (b, s, d), jnp.float32)
    emb = {"table": jax.random.normal(jax.random.PRNGKey(1), (v, d))}
    y = jnp.zeros((b, s), jnp.int32)
    mask = jnp.zeros((b, s)).at[0, :4].set(1.0)
    got = L.chunked_cross_entropy(emb, hid, y, tie=True, chunk=4, mask=mask,
                                  compute_dtype=jnp.float32)
    got_full = L.chunked_cross_entropy(emb, hid[:, :4], y[:, :4], tie=True,
                                       chunk=4, compute_dtype=jnp.float32)
    assert float(got) == pytest.approx(float(got_full), rel=1e-5)


def test_moe_routing_conservation():
    """Every kept token's output is the prob-weighted sum of its experts'
    outputs; capacity 1.0+ with uniform router keeps ~all tokens."""
    key = jax.random.PRNGKey(0)
    spec = MoESpec(d_model=16, num_experts=4, top_k=2, d_ff_expert=32,
                   capacity_factor=2.0, compute_dtype=jnp.float32)
    p = init_moe(key, spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    out, aux, _ = moe_forward(p, spec, x)
    assert out.shape == x.shape
    assert jnp.isfinite(out).all() and float(aux) > 0
    # reference dense computation of the same routing
    t = 16
    xt = x.reshape(t, 16)
    logits = xt @ p["router"]["w"]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    def expert(e, h):
        g = h @ p["w_gate"][e]
        u = h @ p["w_up"][e]
        return (jax.nn.silu(g) * u) @ p["w_down"][e]
    want = jnp.zeros_like(xt)
    for ti in range(t):
        acc = jnp.zeros((16,))
        for j in range(2):
            acc += top_p[ti, j] * expert(int(top_e[ti, j]), xt[ti])
        want = want.at[ti].set(acc)
    np.testing.assert_allclose(np.asarray(out.reshape(t, 16)),
                               np.asarray(want), atol=1e-4, rtol=1e-3)


def test_moe_capacity_drops_overflow():
    key = jax.random.PRNGKey(0)
    spec = MoESpec(d_model=8, num_experts=2, top_k=1, d_ff_expert=16,
                   capacity_factor=0.5, compute_dtype=jnp.float32)
    p = init_moe(key, spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8), jnp.float32)
    out, _, rows = moe_forward(p, spec, x)
    # some tokens must be dropped (zero contribution) at cf=0.5
    norms = jnp.linalg.norm(out.reshape(16, 8), axis=-1)
    assert int(jnp.sum(norms == 0.0)) >= 1
    # ... and the layer counts only the pairs it kept.
    assert int(rows) == 16 - int(jnp.sum(norms == 0.0))


def _moe_pair(scoring, **kw):
    """A grouped-dispatch MoE layer and the same layer over dropless
    buffers (plain JAX, differentiated by JAX itself), float32."""
    spec = MoESpec(d_model=16, num_experts=4, top_k=2, d_ff_expert=32,
                   num_shared_experts=1, scoring=scoring, routed_scale=1.7,
                   dropless=True, compute_dtype=jnp.float32, **kw)
    p = init_moe(jax.random.PRNGKey(2), spec)
    if scoring == "sigmoid":
        p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(3),
                                                   (4,))
    return p, spec, dataclasses.replace(spec, grouped=True)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_grouped_dispatch_matches_dropless_buffers(scoring):
    """Forward, routed rows and every gradient (input, router and expert
    weights) of the grouped dispatch equal the buffers', also per slot
    under ``vmap`` with the weights shared (the folded dispatch)."""
    p, buffers, grouped = _moe_pair(scoring)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 2, 8, 16))
    ct = jax.random.normal(jax.random.PRNGKey(5), (3, 2, 8, 16))

    def loss(spec):
        def f(p, x, ct):
            out, aux, _ = moe_forward(p, spec, x)
            return jnp.sum(out * ct) + aux
        return f

    with jax.default_matmul_precision("highest"):
        for a, b in zip(moe_forward(p, grouped, x[0]),
                        moe_forward(p, buffers, x[0])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        assert int(moe_forward(p, grouped, x[0])[2]) == 2 * 8 * 2
        for argnums in (1, (0, 1)):
            got = jax.vmap(jax.grad(loss(grouped), argnums),
                           (None, 0, 0))(p, x, ct)
            want = jax.vmap(jax.grad(loss(buffers), argnums),
                            (None, 0, 0))(p, x, ct)
            for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                    jax.tree.leaves(want)):
                np.testing.assert_allclose(
                    a, b, rtol=1e-4, atol=1e-5,
                    err_msg=jax.tree_util.keystr(path))
        for k in ("w_gate", "w_up", "w_down"):
            assert float(jnp.max(jnp.abs(got[0][k]))) > 0


def test_moe_model_trains_its_experts():
    """The smoke Moonlight (sigmoid router, grouped dispatch over a held
    share) gives its expert weights the gradient JAX gives the same model
    over dropless buffers holding every expert, restricted to the share."""
    cfg = dataclasses.replace(get_smoke_config("moonshot_v1_16b_a3b"),
                              compute_dtype="float32")
    held = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=2))
    full = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, grouped=False))
    params = build_model(full).init(jax.random.PRNGKey(6))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 12), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    moe = params["segments"][1]["0_attn"]["moe"]
    # Experts 2 and 3 never contribute: their down projections are zero.
    moe["w_down"] = moe["w_down"].at[:, 2:].set(0.0)
    share = jax.tree.map(lambda a: a, params)
    for k in ("w_gate", "w_up", "w_down"):
        share["segments"][1]["0_attn"]["moe"][k] = moe[k][:, :2]
    with jax.default_matmul_precision("highest"):
        (l1, r1), g1 = jax.value_and_grad(
            lambda q: tf.lm_loss(q, held, batch, with_rows=True),
            has_aux=True)(share)
        (l0, r0), g0 = jax.value_and_grad(
            lambda q: tf.lm_loss(q, full, batch, with_rows=True),
            has_aux=True)(params)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    assert int(r0) == 2 * 12 * cfg.moe.top_k      # one MoE layer, all held
    assert 0 < int(r1) < int(r0)
    m1 = g1["segments"][1]["0_attn"]["moe"]
    m0 = g0["segments"][1]["0_attn"]["moe"]
    for k in ("w_gate", "w_up", "w_down"):
        assert float(jnp.max(jnp.abs(m1[k]))) > 0
        np.testing.assert_allclose(m1[k], m0[k][:, :2], rtol=1e-4,
                                   atol=1e-6)
    for k in ("router", "shared"):
        for a, b in zip(jax.tree.leaves(m1[k]), jax.tree.leaves(m0[k])):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
