"""``moonlight_16b_a3b`` as the program runs it: the zoo model of
``repro.models`` at the configuration's widths and cut (latent attention,
a leading dense layer, sigmoid-routed MoE layers holding 8 of 64 experts),
trained as LoRA adapters by ``repro.fl.adapters.lora_view`` over the
frozen base the harness builds and hands over.  The loss is a
``FrozenLoss``: the fleet step takes the base as an argument.  The fleet
trains, hops and mixes the adapter tree alone."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Held-out rows per call of the reference's test loss.
EVAL_BLOCK = 4
# Client slots the reference trains at once.
SLOT_BLOCK = 2
# Held-out rows per step of the program's evaluation.
EVAL_ROWS = 8
# The configuration computes its products in bfloat16; the control rounds
# the operands of every product to fp8 (e4m3) instead.
CONTROL = {"compute_dtype": "float8_e4m3fn", "param_dtype": "float32"}


def model_config(conf: dict):
    """The program's ``ModelConfig`` of the configuration (published keys,
    the cut of ``reduced``)."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        first_k_dense=conf["first_k_dense_replace"],
        mla=MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                      qk_nope_head_dim=conf["qk_nope_head_dim"],
                      qk_rope_head_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        moe=MoEConfig(num_experts=conf["experts_routed"],
                      top_k=conf["num_experts_per_tok"],
                      d_ff_expert=conf["moe_intermediate_size"],
                      num_shared_experts=conf["n_shared_experts"],
                      scoring=conf["scoring_func"],
                      routed_scale=conf["routed_scaling_factor"],
                      grouped=True, experts_held=conf["n_routed_experts"]),
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        compute_dtype=conf["compute_dtype"])


def _shapes(tree):
    return jax.tree.map(lambda a: a.shape, tree)


def program(conf: dict, mix: dict, frozen):
    from repro.fl.adapters import lora_merge, lora_view
    from repro.models import build_model
    model = build_model(model_config(conf))
    if _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0))) != \
            _shapes(frozen):
        raise ValueError("the frozen tree's layout is not the program's base")
    view = lora_view(model, frozen, conf["lora_rank"],
                     tuple(conf["lora_targets"]))
    # The harness jits the evaluation with the base in its closure; through
    # references the base is an argument of that program too, not a
    # constant of it.
    refs = jax.tree.map(jax.new_ref, frozen)

    def evaluate(adapter, x, y):
        params = lora_merge(jax.tree.map(lambda r: r[...], refs), adapter)
        tok = x.astype(jnp.int32)
        rows = max(b for b in range(1, EVAL_ROWS + 1)
                   if tok.shape[0] % b == 0)
        loss, acc = jax.lax.map(
            lambda t: model.loss(params, {"tokens": t[:, :-1],
                                          "labels": t[:, 1:]},
                                 with_accuracy=True),
            tok.reshape((-1, rows) + tok.shape[1:]))
        return jnp.mean(acc), jnp.mean(loss)

    def shapes():
        return jax.eval_shape(view.init_fn, jax.random.PRNGKey(0))
    return view.loss_fn, evaluate, shapes
