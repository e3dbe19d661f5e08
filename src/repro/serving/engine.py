"""Stage-split execution: the compute leaf of the serving data plane.

The control plane (MILP) emits a PipelinePlan; this module materializes its
partitions as jitted per-stage functions over block ranges so they can
actually *run* on local devices — boundary activations are quantized
(boundary_quant kernel) before cross-device transfer, mirroring the paper's
fp32->fp16 trick (section 6 / DESIGN.md section 6).

Scheduling, admission and overlapped dispatch live in `repro.dataplane`
(DESIGN.md section 3); this file only knows how to compute a stage.
`ServingEngine` remains as a thin synchronous wrapper used by older tests and
quickstarts — new code should drive `repro.dataplane.DataPlane`.

Stage splitting maps a model's block graph onto partitions:
  block 0           = embedding (+ modality frontend)
  blocks 1..L       = sequence layers
  block L+1         = final norm + head
A stage spanning blocks [i, j) embeds iff i == 0 and unembeds iff j == n.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import PipelinePlan
from repro.core.types import ModelProfile, Request
from repro.kernels.boundary_quant import ops as bq_ops
from repro.models import transformer as tfm
from repro.models.common import ModelConfig, NO_SHARDING, rms_norm
from repro.models.model_zoo import build_model


def split_stages(cfg: ModelConfig, block_ranges: list[tuple[int, int]],
                 layer_block_map: list[tuple[int, int]]):
    """Build per-stage apply functions for a dense-family model.

    `layer_block_map[b] = (layer_start, layer_end)` for each pre-partitioned
    block b (0 = embed, last = head).  Each stage closure takes (params,
    carry) where carry is tokens for stage 0 and hidden states afterwards,
    and is named `stage_b{i}_{j}` for its block range [i, j).

    A stage scans the indices of its layer range and indexes each stacked
    leaf of `params["layers"]` in the scan body, so the range is read in
    place from the bound tree: a slice `a[lstart:lend]` fed to the scan
    would be copied out of the parameter buffers on every call.
    """
    model = build_model(cfg)
    n_blocks = len(layer_block_map)

    def make_stage(i: int, j: int) -> Callable:
        lo = layer_block_map[i][0]
        hi = layer_block_map[j - 1][1]

        def stage(params: dict, carry):
            rules = NO_SHARDING
            if i == 0:
                x = tfm.embed_tokens(cfg, rules, params, carry)
                lstart, lend = 0, hi
            else:
                x = carry
                lstart, lend = lo, hi
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))

            def body(x, layer):
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, layer,
                                                           keepdims=False),
                    params["layers"])
                x, _ = tfm.layer_full(cfg, rules, lp, x, positions)
                return x, None

            x, _ = jax.lax.scan(body, x, jnp.arange(lstart, lend))
            if j == n_blocks:
                x = rms_norm(x, params["final_norm"], cfg.norm_eps)
                return tfm.unembed(cfg, rules, params, x)
            return x

        # the program's name in compiled modules and device traces:
        # jit_stage_b{i}_{j}, one per block range
        stage.__name__ = stage.__qualname__ = f"stage_b{i}_{j}"
        return stage

    return model, [make_stage(i, j) for i, j in block_ranges]


def layer_block_map_from_profile(profile: ModelProfile, n_layers: int
                                 ) -> list[tuple[int, int]]:
    """Map a ModelProfile's blocks to the (layer_start, layer_end) ranges
    `split_stages` expects.

    Profiles are built from `model_zoo.layer_costs`, whose cost index 0 is the
    embedding and index L+1 the head; model layer k lives at cost index k+1.
    Embedding/unembedding are implied by block position (first/last), so the
    map only carries sequence-layer ranges, clamped into [0, n_layers].
    """
    def clamp(i: int) -> int:
        return max(0, min(n_layers, i))

    return [(clamp(b.layer_start - 1), clamp(b.layer_end - 1))
            for b in profile.blocks]


@dataclass
class StageExecutor:
    """One partition pool: a jitted stage function bound to its params.

    On a single host all pool members are co-resident, so one executor (one
    compiled program) serves the whole pool; member identity only matters to
    the reservation scheduler, which tracks per-vdev timelines.
    """

    stage_fn: Callable
    params: dict
    quantize_boundary: bool = True
    device: Any = None  # target jax.Device; None = process default
    _jitted: Callable | None = None

    def __post_init__(self):
        self._jitted = jax.jit(self.stage_fn)

    def __call__(self, carry):
        out = self._jitted(self.params, carry)
        return out

    def transfer(self, x: jax.Array) -> jax.Array:
        """Boundary transfer into this stage: int8-quantize on the sender,
        move, dequantize on the receiver (paper section 6 / DESIGN.md).

        Skipped for any integer carry (token ids and other index tensors are
        exact already) and when sender and receiver share a device — the
        quantize->dequantize round-trip without a wire in between is pure
        overhead and pure error.
        """
        target = self.device or jax.devices()[0]
        src_devices = x.devices() if hasattr(x, "devices") else {target}
        if src_devices == {target}:
            return x  # co-resident: nothing to move, nothing to compress
        if not self.quantize_boundary or jnp.issubdtype(x.dtype, jnp.integer):
            return jax.device_put(x, target)
        q, scale = bq_ops.quantize(x)
        q = jax.device_put(q, target)
        scale = jax.device_put(scale, target)
        return bq_ops.dequantize(q, scale, x.dtype)


@dataclass
class ServingEngine:
    """Synchronous wrapper kept for quickstarts/back-compat; `serve()` routes
    through the data plane's PoolDispatcher so batches overlap across stages
    instead of running one at a time (single-host: pools are co-resident)."""

    cfg: ModelConfig
    pipeline: PipelinePlan
    executors: list[list[StageExecutor]]  # [stage][pool member]
    rr: list[int] = field(default_factory=list)

    def __post_init__(self):
        self.rr = [0] * len(self.executors)

    def infer(self, tokens: jax.Array) -> jax.Array:
        """Run one batch through the pipeline (round-robin pool members)."""
        carry: Any = tokens
        for si, pool in enumerate(self.executors):
            member = pool[self.rr[si] % len(pool)]
            self.rr[si] += 1
            if si > 0:
                carry = member.transfer(carry)
            carry = member(carry)
        return carry

    def serve(self, requests: list[Request], batch_size: int | None = None,
              seq_len: int = 128) -> dict:
        """Batch + run requests with overlapped dispatch; returns wall-clock
        latency stats plus the in-flight high-water mark."""
        from repro.dataplane.dispatcher import PoolDispatcher

        bs = batch_size or self.pipeline.batch_size
        disp = PoolDispatcher({0: [pool[0] for pool in self.executors]})
        submits: list[tuple[int, float, int]] = []
        for i in range(0, len(requests), bs):
            chunk = requests[i : i + bs]
            tokens = jnp.ones((len(chunk), seq_len), jnp.int32)
            job_id = disp.submit_chain(0, tokens)
            submits.append((job_id, time.perf_counter(), len(chunk)))
        done = disp.drain_all()
        by_job = {c.job_id: c for c in done}
        lat = [by_job[j].done_wall - t0 for j, t0, _ in submits if j in by_job]
        return {
            "served": sum(n for _, _, n in submits),
            "batches": len(submits),
            "mean_batch_latency_s": float(np.mean(lat)) if lat else 0.0,
            "p99_batch_latency_s": float(np.percentile(lat, 99)) if lat else 0.0,
            "inflight_hwm": disp.inflight_hwm,
        }


def build_engine(cfg: ModelConfig, pipeline: PipelinePlan,
                 layer_block_map: list[tuple[int, int]], key) -> ServingEngine:
    ranges = [(s.block_start, s.block_end) for s in pipeline.stages]
    model, stage_fns = split_stages(cfg, ranges, layer_block_map)
    params = model.init(key)
    executors = []
    for sp, fn in zip(pipeline.stages, stage_fns):
        # one compiled executor shared by every co-resident pool member
        # (per-member jits would re-trace the identical partition n_vdev times)
        shared = StageExecutor(stage_fn=fn, params=params)
        executors.append([shared] * sp.n_vdev)
    return ServingEngine(cfg=cfg, pipeline=pipeline, executors=executors)
