"""``moonlight_16b_a3b``'s program against its plain reference, on seeded
random weights at the configuration's CPU sizes: latent attention, the
held-expert MoE layer (with the selection bias moving which experts are
picked), the shares of the experts adding up to the uncut layer, the
grouped-product kernel against ``jax.lax.ragged_dot``, and the fleet step
taking the frozen base as an argument."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import harness as H
from repro.fl.adapters import lora_merge
from repro.kernels import ops as kernel_ops
from repro.kernels.moe_gmm import GMM_ROWS
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import transformer as tf

CONF, REF, GLUE = H.load_config("moonlight_16b_a3b")
SMALL = H.cpu_sizes(CONF)            # float32 products at the CPU sizes
TOL = dict(rtol=2e-5, atol=2e-5)


def _layer(fz, seg: int, i: int = 0):
    (name, stack), = fz["segments"][seg].items()
    return name, jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)


def _specs(conf):
    return tf.specs_for(GLUE.model_config(conf))


def _hidden(conf, key, tokens=24, batch=2):
    return jax.random.normal(jax.random.PRNGKey(key),
                             (batch, tokens, conf["hidden_size"]))


@pytest.mark.parametrize("seg", [0, 1])
def test_mla_matches_the_reference(seg):
    fz = REF.frozen(SMALL, jax.random.PRNGKey(0))
    ad = REF.init(SMALL, jax.random.PRNGKey(1))
    name, base = _layer(fz, seg)
    lora = jax.tree.map(lambda a: a[0], ad["segments"][seg][name]["attn"])
    attn = _specs(SMALL)[0]
    assert isinstance(attn, mla_lib.MLASpec)
    h = _hidden(SMALL, 2)
    with jax.default_matmul_precision("highest"):
        got = mla_lib.mla_forward(lora_merge(base["attn"], lora), attn, h)
        want = REF._attention(h, base["attn"], lora, SMALL, jnp.float32)
    np.testing.assert_allclose(got, want, **TOL)


def _moe_base(bias_scale=1.0):
    fz = REF.frozen(SMALL, jax.random.PRNGKey(3))
    _, base = _layer(fz, 1)
    moe = base["moe"]
    moe["router_bias"] = moe["router_bias"] * bias_scale
    return moe


@pytest.mark.parametrize("bias_scale", [0.0, 1.0, 10.0])
def test_held_expert_layer_matches_the_reference(bias_scale):
    moe = _moe_base(bias_scale)
    spec = _specs(SMALL)[2]
    assert spec.experts_held < spec.num_experts
    h = _hidden(SMALL, 4)
    with jax.default_matmul_precision("highest"):
        got, aux, rows = moe_lib.moe_forward(moe, spec, h)
        want = REF.moe_layer(h.reshape(-1, h.shape[-1]), moe, SMALL,
                             jnp.float32).reshape(h.shape)
    np.testing.assert_allclose(got, want, **TOL)
    assert float(aux) == 0.0
    # The held experts take part: some token picks one of them.
    top, _ = moe_lib.route_sigmoid(moe, spec, h.reshape(-1, h.shape[-1]))
    assert int(jnp.sum(top < spec.experts_held)) > 0
    # The layer counts the pairs its held experts computed.
    assert int(rows) == int(jnp.sum(top < spec.experts_held))


def test_the_selection_bias_moves_the_picks_and_not_the_weights():
    spec = _specs(SMALL)[2]
    xt = _hidden(SMALL, 5).reshape(-1, SMALL["hidden_size"])
    plain, biased = _moe_base(0.0), _moe_base(10.0)
    top0, w0 = moe_lib.route_sigmoid(plain, spec, xt)
    top1, w1 = moe_lib.route_sigmoid(biased, spec, xt)
    assert bool(jnp.any(top0 != top1))
    # Weights are the unbiased scores of the picks, normalised and scaled.
    logits = xt @ biased["router"]["w"].astype(jnp.float32)
    s = jnp.take_along_axis(jax.nn.sigmoid(logits), top1, axis=-1)
    np.testing.assert_allclose(
        w1, s / s.sum(-1, keepdims=True) * SMALL["routed_scaling_factor"],
        rtol=1e-5)
    # Biased picks are the top of score plus bias.
    sb = jax.nn.sigmoid(logits) + biased["router_bias"]
    assert bool(jnp.all(jnp.take_along_axis(sb, top1, -1).min(-1)
                        >= jnp.sort(sb, -1)[:, -spec.top_k]))


def test_the_shares_add_up_to_the_uncut_layer():
    """Each of the experts_routed / held shares computes its experts' part
    (its router columns first, as the chip holding them sees them); the
    parts, with the shared experts counted once, are the whole layer of
    the uncut reference, which holds every expert."""
    routed = SMALL["experts_routed"]
    whole = dict(SMALL, n_routed_experts=routed)
    fz = REF.frozen(whole, jax.random.PRNGKey(6))
    _, base = _layer(fz, 1)
    moe = base["moe"]
    moe["router_bias"] = moe["router_bias"] * 10.0
    held = SMALL["n_routed_experts"]
    spec = _specs(SMALL)[2]
    xt = _hidden(SMALL, 7).reshape(-1, SMALL["hidden_size"])
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(xt, moe, whole, jnp.float32)
        shared = REF._swiglu(xt, moe["shared"], jnp.float32)
        total = shared
        for first in range(0, routed, held):
            share = dict(moe)
            share["router"] = {"w": jnp.roll(moe["router"]["w"], -first, 1)}
            share["router_bias"] = jnp.roll(moe["router_bias"], -first)
            for k in ("w_gate", "w_up", "w_down"):
                share[k] = moe[k][first:first + held]
            part, _, _ = moe_lib.moe_forward(share, spec, xt[None])
            total = total + part[0] - shared
    np.testing.assert_allclose(total, want, **TOL)


@pytest.mark.parametrize("transpose_w", [False, True])
def test_moe_gmm_interpret_matches_ragged_dot(transpose_w):
    key = jax.random.PRNGKey(8)
    e, k, n = 3, 64, 256
    sizes = jnp.array([2, 0, 3], jnp.int32) * GMM_ROWS
    rows = 7 * GMM_ROWS                     # two tiles of padding
    used = int(sizes.sum())
    x = jax.random.normal(key, (rows, k)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (e, n, k) if transpose_w else (e, k, n)
                          ).astype(jnp.bfloat16)
    wt = jnp.swapaxes(w, 1, 2) if transpose_w else w

    def ragged(x):
        return jax.lax.ragged_dot(x, wt, sizes,
                                  preferred_element_type=jnp.float32)

    def kernel(x):
        return kernel_ops.moe_gmm(x, w, sizes, transpose_w=transpose_w,
                                  implementation="pallas_interpret")

    f32 = lambda a: np.asarray(a[:used], np.float32)      # noqa: E731
    np.testing.assert_allclose(f32(kernel(x)),
                               f32(ragged(x).astype(jnp.bfloat16)),
                               rtol=1e-2, atol=1e-2)
    dy = jax.random.normal(jax.random.fold_in(key, 2),
                           (rows, n)).astype(jnp.bfloat16)
    dy = dy.at[used:].set(0)
    got = jax.vjp(kernel, x)[1](dy)[0]
    want = jax.vjp(ragged, x)[1](dy.astype(jnp.float32))[0]
    np.testing.assert_allclose(f32(got), f32(want), rtol=2e-2, atol=5e-2)


def _fleet(conf, loss_fn):
    from repro.fl.executors import FleetExecutor
    from repro.fl.server import FLConfig
    return FleetExecutor(loss_fn, [], FLConfig(
        strategy="feddif", num_clients=3, num_models=3, lr=0.05,
        momentum=0.9, batch_size=1))


def _step_args(conf, key=9, clients=3):
    ad = REF.init(conf, jax.random.PRNGKey(key))
    p = jax.tree.map(lambda a: jnp.broadcast_to(a, (clients,) + a.shape), ad)
    mom = jax.tree.map(jnp.zeros_like, p)
    x = jax.random.randint(jax.random.PRNGKey(key + 1),
                           (clients, 1, conf["data"]["seq"] + 1), 0,
                           conf["vocab_size"])
    batch = {"x": x, "y": jnp.zeros((clients, 1), jnp.int32)}
    return p, mom, batch, jnp.array([True, True, False])


def test_fleet_step_takes_the_frozen_base_as_an_argument():
    fz = REF.frozen(SMALL, jax.random.PRNGKey(10))
    loss_fn, _, _ = GLUE.program(SMALL, {}, fz)
    ex = _fleet(SMALL, loss_fn)
    args = _step_args(SMALL)
    got = ex._step(*jax.tree.map(jnp.copy, args), None, *ex._frozen)
    # The closure form: the same loss with the base as a constant.
    closed = _fleet(SMALL, lambda p, b: loss_fn(p, b))
    assert closed._frozen == ()
    want = closed._step(*jax.tree.map(jnp.copy, args), None)
    assert len(want) == 3 and len(got) == 4
    for a, b in zip(jax.tree.leaves(got[:3]), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # The frozen form's step also gives each slot's routed rows.
    rows = got[3]["fl.exec.moe_rows"]
    assert rows.shape == (3,) and rows.dtype == jnp.int32

    def text(vocab):
        conf = dict(SMALL, vocab_size=vocab,
                    data=dict(SMALL["data"], vocab=vocab))
        base = REF.frozen(conf, jax.random.PRNGKey(10))
        step = _fleet(conf, GLUE.program(conf, {}, base)[0])
        low = step._step.lower(*_step_args(conf), None, *step._frozen)
        return (sum(a.size for a in jax.tree.leaves(base)),
                len(re.sub(r"[0-9]+", "0", low.as_text())))

    # A base four times the vocabulary leaves the program as long, with
    # every number in its text written as one digit: no constant holds it.
    (small, small_text), (big, big_text) = text(512), text(2048)
    assert big == small + 2 * (2048 - 512) * SMALL["hidden_size"]
    assert big_text <= small_text


def test_a_recorded_session_counts_the_routed_rows():
    """``fl.exec.moe_rows`` adds up the pairs each slot's MoE layers route
    to the held experts, in every step of the session, with no compile in
    the recorded session."""
    from repro import obs
    from repro.fl.executors import FleetExecutor
    from repro.fl.server import FLConfig
    fz = REF.frozen(SMALL, jax.random.PRNGKey(11))
    loss_fn, _, _ = GLUE.program(SMALL, {}, fz)
    p, _, batch, _ = _step_args(SMALL, key=12)
    rows = [{"x": batch["x"][i], "y": batch["y"][i]} for i in range(3)]
    ex = FleetExecutor(loss_fn, [lambda r=r: [r, r] for r in rows],
                       FLConfig(strategy="feddif", num_clients=3,
                                num_models=3, lr=0.0, momentum=0.0,
                                batch_size=1))
    # A first session compiles the step; a recorded one compiles nothing
    # more: the counts are kept unread until the record is taken.
    ex._session(jax.tree.map(jnp.copy, p), np.ones(3, bool))
    with obs.recording():
        ex._session(jax.tree.map(jnp.copy, p), np.ones(3, bool))
        rec = obs.snapshot()
    assert rec.compiles == {}
    # lr 0: both steps of a slot see the same adapters, so route alike.
    want = 2 * sum(int(loss_fn.fn(jax.tree.map(lambda a: a[i], p), r,
                                  fz)[1]["fl.exec.moe_rows"])
                   for i, r in enumerate(rows))
    tokens = 3 * 2 * SMALL["data"]["seq"] * SMALL["num_experts_per_tok"]
    assert 0 < want < tokens
    assert rec.counters["fl.exec.moe_rows"] == want
    assert rec.counters["fl.exec.steps"] == 2


def test_the_roofline_counts_the_routed_rows_the_program_counted():
    """``moe_gmm_roofline`` reads the program's routed rows and steps: five
    products of 2·rows·K·N FLOPs each a MoE layer, over the kernel's device
    time; without the counter it reports nothing."""
    from perf.metrics import moe_gmm_roofline as M
    from perf.peaks import PEAKS
    from repro.obs import Record

    class Trace:
        def op_time(self, pattern):
            return 0.5 if pattern == M.KERNEL else None

    d, f = CONF["hidden_size"], CONF["moe_intermediate_size"]
    layers = CONF["num_hidden_layers"] - CONF["first_k_dense_replace"]
    rows = 3 * 16 * 1024 * 6 * layers // 8
    ctx = {"trace": Trace(), "conf": CONF, "rounds": 1,
           "peaks": PEAKS["TPU v5 lite"],
           "program": Record(spans=[], counters={"fl.exec.moe_rows": rows,
                                                 "fl.exec.steps": 3},
                             compiles={})}
    flops = 16 * rows * d * f
    assert M.moe_gmm_cost(rows, d, f, 8, 3 * layers)[0] == flops
    want = 100 * flops / PEAKS["TPU v5 lite"]["bf16_flops_per_s"] / 0.5
    assert M.read(ctx) == pytest.approx(want)
    ctx["program"] = Record(spans=[], counters={"fl.exec.steps": 3},
                            compiles={})
    assert M.read(ctx) is None
