"""Public jit'd wrappers for boundary quantization.

The Pallas kernels run compiled on every backend but the CPU, which has no
Mosaic lowering and takes interpret mode.  The choice is made per lowering
platform (`lax.platform_dependent`), not from `jax.default_backend()`, so a
program compiled for a TPU from a CPU-only process still gets the kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import kernel


def _divisor_block(rows: int, target: int = 256) -> int:
    for b in range(min(target, rows), 0, -1):
        if rows % b == 0:
            return b
    return 1


def _per_platform(fn, *args):
    """`fn(*args, interpret=...)`: interpret mode on CPU only."""
    return jax.lax.platform_dependent(
        *args,
        cpu=partial(fn, interpret=True),
        default=partial(fn, interpret=False),
    )


@jax.jit
def quantize(x):
    """x: (..., D) -> (int8 (..., D), f32 scales (..., 1))."""
    shape = x.shape
    rows = max(1, x.size // shape[-1])
    q, s = _per_platform(
        partial(kernel.quantize, block_rows=_divisor_block(rows)),
        x.reshape(-1, shape[-1]),
    )
    return q.reshape(shape), s.reshape(shape[:-1] + (1,))


@partial(jax.jit, static_argnames=("dtype",))
def dequantize(q, scale, dtype=jnp.bfloat16):
    shape = q.shape
    rows = max(1, q.size // shape[-1])
    out = _per_platform(
        partial(kernel.dequantize, dtype=dtype,
                block_rows=_divisor_block(rows)),
        q.reshape(-1, shape[-1]), scale.reshape(-1, 1),
    )
    return out.reshape(shape)
