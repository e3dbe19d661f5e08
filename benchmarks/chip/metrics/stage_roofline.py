"""stage_roofline: the stage programs' least time from shapes (per call, the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, for the
requests the call served: the rows padding a batch to its power-of-two
bucket are not needed work) over their device time in the profiler trace
(`jit_stage` modules), in percent.  Calls pair with the window's dispatches
one to one; where they do not, there is nothing to read."""

import sys

import costs


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["peak"] is None or not tr["stage_s"]:
        return None
    conf, layers = ctx["config"], ctx["stage_layers"]
    if tr["stage_calls"] != len(ctx["dispatches"]) * len(layers):
        print(f"[stage_roofline] {tr['stage_calls']} stage calls traced for "
              f"{len(ctx['dispatches'])} dispatches of {len(layers)} stages",
              file=sys.stderr)
        return None
    least = {"flops": 0.0, "bytes": 0.0}
    for d in ctx["dispatches"]:
        for lay, emb, unemb in layers:
            t, bound = costs.least_time(
                *costs.stage_cost(conf["model"], conf["seq_len"],
                                  d.batch_size, lay, emb, unemb),
                ctx["peak"])
            least[bound] += t
    total = least["flops"] + least["bytes"]
    print(f"[stage_roofline] least {total:.6f} s ({least['flops']:.6f} s "
          f"bound by flops, {least['bytes']:.6f} s by bytes) of "
          f"{tr['stage_s']:.6f} s of stage programs", file=sys.stderr)
    return 100.0 * total / tr["stage_s"]
