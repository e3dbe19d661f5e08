"""XLA compilation as the entry points see it: where the persistent cache
lives, and how many programs a stretch of work compiled.

`enable_compile_cache()` is called once by each entry point (`chip_smoke.py`,
the `examples/` scripts, `benchmarks/run.py`) before it compiles anything;
library code never calls it.  `count_compiles()` counts backend
compilations (persistent-cache hits included) inside a ``with`` block, so a
serving window can be shown to compile nothing.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: src/repro/api/compilation.py -> parents[3]
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here.  Otherwise the cache goes to the fixed
    `DEFAULT_CACHE_DIR`: a directory named after a temp dir, a pid or the
    time would never be found again by the next run."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


class CompileCount:
    """Backend compilations seen so far by an open `count_compiles()`."""

    def __init__(self) -> None:
        self.n = 0

    def _on_event(self, event: str, duration_s: float, **_kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.n += 1


@contextlib.contextmanager
def count_compiles():
    """``with count_compiles() as c: ...`` — `c.n` backend compilations
    happened inside the block (persistent-cache hits count too: each one
    still means a program shape that was not warm in this process)."""
    counter = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(counter._on_event)
    try:
        yield counter
    finally:
        jax.monitoring.unregister_event_duration_listener(counter._on_event)
