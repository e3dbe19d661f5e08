"""stage_wall_inflation: for each stage program, the window's sum of the
walls measured feedback observed for it (`FeedbackController`
`measured_wall_s`) over its device seconds in the profiler trace (module
`jit_stage_b{i}_{j}`, its block range); the largest over the stages.  1 is
a wall equal to the chip's time for the stage; a wall that also holds time
queued behind another stage's program reads above it.  A stage's traced
calls pair one to one with its observed walls; where they do not, or where
the program keeps no such counters, there is nothing to read."""

import sys


def read(ctx):
    spans, fb = ctx.get("spans"), ctx.get("feedback")
    if not spans or not spans["modules"] or not fb:
        return None
    walls: dict[str, list] = {}
    for st in fb:
        w = walls.setdefault("jit_stage_b{}_{}".format(*st["blocks"]),
                             [0.0, 0])
        w[0] += st["measured_wall_s"]
        w[1] += st["observed"]
    worst = None
    for name, (wall_s, observed) in walls.items():
        dev = spans["modules"].get(name)
        if dev is None or dev["n"] != observed or dev["s"] <= 0:
            print(f"[stage_wall_inflation] {name}: "
                  f"{dev['n'] if dev else 0} calls traced for {observed} "
                  "observed walls", file=sys.stderr)
            return None
        worst = max(worst or 0.0, wall_s / dev["s"])
    return worst
