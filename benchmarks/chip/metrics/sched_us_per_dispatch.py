"""sched_us_per_dispatch: host microseconds of Algorithm 1's planning calls
(the program's `ppipe.schedule` spans, `DataPlane._run_scheduler`, summed
from the profiler trace by `span_reduce`) per window dispatch.  Nothing to
read where the trace has no such spans."""


def read(ctx):
    spans = (ctx.get("spans") or {}).get("spans", {})
    n = len(ctx["dispatches"])
    if "ppipe.schedule" not in spans or not n:
        return None
    return 1e6 * spans["ppipe.schedule"]["s"] / n
