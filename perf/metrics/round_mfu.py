"""Model FLOPs of the rows trained in the window over the window and the
chips' bf16 peak.  Rows are counted as the client batch draws hand them
out (the fleet's padded slots are not drawn, so they do not count); the
FLOPs per row are the configuration's (forward and backward, nothing
recomputed)."""


def read(ctx):
    if not ctx["rows"]:
        return None
    flops = ctx["rows"] * ctx["ref"].train_flops_per_row(ctx["conf"],
                                                          ctx["mix"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["devices"]
    return 100.0 * flops / (ctx["window_s"] * peak)
