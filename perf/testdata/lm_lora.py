"""``lm_lora`` as the program runs it: the ``lm`` task of
``repro.fl.models``, trained as LoRA adapters over the frozen base the
harness builds and hands over, as ``repro.fl.adapters.make_adapter_view``
closes over a base of its own.  The fleet trains, hops and mixes the
adapter tree alone."""
from __future__ import annotations

import jax

# Held-out rows per call of the reference's test loss.
EVAL_BLOCK = 64
# Client slots the reference trains at once: the whole fleet.
SLOT_BLOCK = 8
# On the CPU every product runs in float32; the control rounds the
# operands of every product to bfloat16.
CONTROL = {"compute_dtype": "bfloat16", "param_dtype": "float32"}


def _layout(tree):
    return jax.tree.map(lambda a: (a.shape, a.dtype), tree)


def program(conf: dict, mix: dict, frozen):
    from repro.fl.models import build_task_model
    model = build_task_model("lm")
    base, lora = jax.eval_shape(
        lambda: model.split(model.init(jax.random.PRNGKey(0))))
    if _layout(base) != _layout(frozen):
        raise ValueError("the frozen tree's layout is not the program's base")

    def loss_fn(adapter, batch):
        return model.loss(model.merge(frozen, adapter), batch)

    def evaluate(adapter, x, y):
        params = model.merge(frozen, adapter)
        return model.accuracy(params, x, y), model.loss(params,
                                                        {"x": x, "y": y})

    def shapes():
        return lora
    return loss_fn, evaluate, shapes
