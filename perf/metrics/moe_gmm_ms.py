"""Device milliseconds per round in the grouped product over the held
experts, the Pallas ``moe_gmm`` kernel (``kernels/moe_gmm.py``): the
forward products of every MoE layer, and in the backward pass the
recomputed gate/up product and the two input-gradient products."""

KERNEL = r"moe_gmm"


def read(ctx):
    t = ctx["trace"].op_time(KERNEL)
    return None if not t else 1e3 * t / ctx["rounds"]
