"""Share of its roofline that the Eq.-11 ``mix_aggregate`` kernel reaches
(``kernels/diffusion.py``).  Each round aggregates once, ``w (1, C) @ x
(C, F)`` over the C client slots and the F parameters of the tree the
fleet trains and mixes (the trained tree of the reference's ``init``; a
frozen tree is never mixed); the bound of a call is the larger of its
FLOPs over the bf16 peak and its bytes over the HBM bandwidth (the bytes
bound it: about 2 FLOPs per 4 bytes)."""

import math

import jax

from perf import flops as F

KERNEL = r"mix_aggregate|_mix_kernel"


def mixed_params(conf: dict, ref) -> int:
    """F, from the shapes of the reference's trained tree."""
    tree = jax.eval_shape(lambda: ref.init(conf, jax.random.PRNGKey(0)))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(tree))


def read(ctx):
    t = ctx["trace"].module_time(KERNEL)
    if t is None:
        t = ctx["trace"].op_time(KERNEL)
    if not t:
        return None
    flops, nbytes = F.mix_aggregate_cost(int(ctx["mix"]["clients"]),
                                         mixed_params(ctx["conf"],
                                                      ctx["ref"]), 1)
    peaks = ctx["peaks"]
    bound = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * ctx["rounds"] * bound / t
