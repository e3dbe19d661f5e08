"""Host spans of the real data plane, on the profiler's clock.

A span is a `jax.profiler.TraceAnnotation`: a TraceMe event on the
profiler's host plane, which shares its clock with the device planes, so
a reduction of one profiler trace can say what the host was doing in each
device idle gap.  With no profiler running a span costs under a
microsecond and records nothing.

The spans are flat and leaf-level: none encloses another, and none
encloses a whole serve call, so each names only its own work.  Names are
fixed strings and any detail goes in the span's args (TraceMe stats), so
spans sum by name:

* `SCHEDULE`: Algorithm 1's planning call (`DataPlane._run_scheduler`);
* `TOKENS`: building a dispatched batch's tokens (`DataPlane._dispatch`);
* `SUBMIT`: enqueueing every stage of a batch (`PoolDispatcher.submit_chain`;
  args: pipeline, batch);
* `WAIT`: the host blocked on one stage's output for measured feedback
  (`PoolDispatcher._measure_through`; args: stage).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation as span

SCHEDULE = "ppipe.schedule"
TOKENS = "ppipe.tokens"
SUBMIT = "ppipe.submit"
WAIT = "ppipe.wait"
SPANS = (SCHEDULE, TOKENS, SUBMIT, WAIT)

__all__ = ["span", "SCHEDULE", "TOKENS", "SUBMIT", "WAIT", "SPANS"]
