"""mfu: model FLOPs of the window's completed requests (counted from shapes
in costs.py) over the serve call's wall seconds at the chip's bf16 peak, in
percent: the whole serving step's share of the peak.  Padding rows, the
layer-slice copy, idle device time and the host loop all count against it."""

import costs


def read(ctx):
    if ctx["peak"] is None:
        return None
    conf = ctx["config"]
    served = sum(1 for o in ctx["outcomes"].values()
                 if o.completion_s is not None)
    flops = served * costs.request_flops(conf["model"], conf["seq_len"])
    return 100.0 * flops / (ctx["wall_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops"])
