"""lat_scale_max: the highest latency scale measured feedback set on any
stage in the window (`FeedbackController.scale_peak`, reset to each
stage's scale at the window's start).  Above 1 the scheduler prices the
stage slower than its calibration; a scale that prices batch 1 past the
SLO drops every request.  Nothing to read where the program keeps no
such counter."""


def read(ctx):
    peaks = [st["scale_peak"] for st in ctx.get("feedback") or ()
             if st["scale_peak"] is not None]
    return max(peaks) if peaks else None
