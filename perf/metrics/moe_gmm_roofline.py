"""Share of its roofline that the grouped product over the held experts
(``moe_gmm``) reaches in the window.  The work counted is what the kernel
ran: the program's counter ``fl.exec.moe_rows`` (the (token, expert) pairs
the MoE layers routed to the held experts, summed on the device over every
client slot of every step, the slots that pad a session included, and
read once after the window) times the five launches of a MoE layer's
training step: the forward gate/up (D -> 2F) and down (F -> D) products,
the gate/up product the backward pass recomputes, and the two
input-gradient products (D -> F and 2F -> D).  The padding of each
expert's rows to a 128-row tile is not counted.  The bound is the larger
of the FLOPs over the bf16 peak and the bytes over the HBM bandwidth (the
FLOPs bound it, about 600 FLOPs a byte).  A program without the counter
reports nothing."""

from perf import program as P

KERNEL = r"moe_gmm"


def moe_gmm_cost(rows: float, d: int, f: int, held: int,
                 launches: int) -> tuple[float, float]:
    """``(flops, bytes)`` of the five products over ``rows`` routed rows
    in ``launches`` calls of each (one per MoE layer and step): bf16 rows
    read and written once a product, each held expert's weights read once
    a call."""
    products = ((d, 2 * f), (f, d), (d, 2 * f), (d, f), (2 * f, d))
    flops = sum(2 * rows * k * n for k, n in products)
    nbytes = sum(2 * rows * (k + n) + 2 * held * k * n * launches
                 for k, n in products)
    return flops, nbytes


def read(ctx):
    t = ctx["trace"].op_time(KERNEL)
    rec = P.record(ctx)
    if not t or rec is None:
        return None
    rows = rec.counters.get("fl.exec.moe_rows")
    steps = rec.counters.get("fl.exec.steps")
    if not rows or not steps:
        return None
    conf = ctx["conf"]
    layers = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    flops, nbytes = moe_gmm_cost(rows, conf["hidden_size"],
                                 conf["moe_intermediate_size"],
                                 conf["n_routed_experts"], steps * layers)
    peaks = ctx["peaks"]
    bound = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
