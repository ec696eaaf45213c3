"""Plain reference of ``cnn_fmnist``: the FMNIST CNN in straightforward
``jax.numpy``, imported from nothing of the program.

Two 3x3 ``SAME`` convolutions (1 -> 16 -> 32 channels), each with a ReLU
and a 2x2 max pool, then a ReLU MLP head 1568 -> 128 -> 10 and the mean
softmax cross-entropy.  ``compute_dtype`` float32 runs every product at
``HIGHEST`` precision; a lower type rounds the operands of every product
to it (one-byte types are then carried in bfloat16, which holds each of
their values exactly) and the activations to bfloat16 (the control).  The
parameter tree is the layout the program trains.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf import flops as F

HIGHEST = jax.lax.Precision.HIGHEST


def init(conf: dict, key) -> dict:
    """Initial weights from a key: the program's distributions."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    c1, c2 = conf["channels"]
    k, side, hid, ncls = (conf["kernel"], conf["side"], conf["hidden"],
                          conf["classes"])
    flat = c2 * (side // 4) ** 2

    def dense(key, a, b):
        return {"w": jax.random.normal(key, (a, b), jnp.float32) / jnp.sqrt(a),
                "b": jnp.zeros((b,), jnp.float32)}
    return {"c1": jax.random.normal(k1, (k, k, 1, c1), jnp.float32) * 0.2,
            "c2": jax.random.normal(k2, (k, k, c1, c2), jnp.float32) * 0.1,
            "head": [dense(k3, flat, hid), dense(k4, hid, ncls)]}


def _prec(dtype):
    return HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def _cast(a, dtype):
    a = a.astype(dtype)
    return a.astype(jnp.bfloat16) if a.dtype.itemsize == 1 else a


def logits(params, x, conf: dict, dtype=jnp.float32):
    prec = _prec(dtype)
    act = jnp.float32 if prec is not None else jnp.bfloat16
    side = conf["side"]
    h = x.reshape(x.shape[0], side, side, 1)
    for name in ("c1", "c2"):
        h = jax.lax.conv_general_dilated(
            _cast(h, dtype), _cast(params[name], dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=prec).astype(act)
        h = jnp.maximum(h, 0)
        b, s, _, c = h.shape                     # 2x2 max pool
        h = h.reshape(b, s // 2, 2, s // 2, 2, c).max(axis=(2, 4))
    h = h.reshape(h.shape[0], -1)
    first, last = params["head"]
    h = (jnp.dot(_cast(h, dtype), _cast(first["w"], dtype), precision=prec,
                 preferred_element_type=jnp.float32)
         + first["b"]).astype(act)
    h = jnp.maximum(h, 0)
    return (jnp.dot(_cast(h, dtype), _cast(last["w"], dtype), precision=prec,
                    preferred_element_type=jnp.float32) + last["b"])


def loss(params, x, y, conf: dict, dtype=jnp.float32):
    z = logits(params, x, conf, dtype).astype(jnp.float32)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def train_flops_per_row(conf: dict, mix: dict) -> int:
    return F.train_flops(F.cnn_forward_flops(
        conf["side"], tuple(conf["channels"]), conf["kernel"],
        conf["hidden"], conf["classes"]))
