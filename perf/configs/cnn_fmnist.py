"""``cnn_fmnist`` as the program runs it: ``build_task_model("cnn", 784,
10)`` of ``repro.fl.models``, its loss on the loaders' ``{"x", "y"}``
batches, and its loss and accuracy on the held-out rows."""
from __future__ import annotations

import jax

# Held-out rows per call of the reference's test loss.
EVAL_BLOCK = 2000
# Client slots the reference trains at once: the whole fleet.
SLOT_BLOCK = 256
# The configuration keeps float32 parameters and computes its products at
# the TPU's default precision, one bfloat16 pass; the control rounds the
# operands of every product to fp8 (e4m3) instead.
CONTROL = {"compute_dtype": "float8_e4m3fn", "param_dtype": "float32"}


def program(conf: dict, mix: dict):
    from repro.fl.models import build_task_model
    model = build_task_model("cnn", conf["dim"], conf["classes"],
                             conf["hidden"])

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def evaluate(params, x, y):
        return model.accuracy(params, x, y), model.loss(params,
                                                        {"x": x, "y": y})

    def shapes():
        return jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return loss_fn, evaluate, shapes
