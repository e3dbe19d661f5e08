"""Compile the served path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached.  These compiles catch what interpret-mode
tests cannot — a Pallas kernel the Mosaic compiler refuses, a wrapper that
silently took interpret mode, a stage program that does not fit one chip.
Nothing runs, so nothing here is a timing or a result check.

The topology is described inside a fixture (never at import) because only
one process at a time may load the TPU library: a worker that is not given
this file must not touch it.  Keep every described-TPU compile in this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import ClusterSpec, ModelSpec, profile_model
from repro.configs import get_config
from repro.kernels.boundary_quant import ops
from repro.serving.engine import layer_block_map_from_profile, split_stages

ROWS = 8 * 256  # batch 8 x the serving sequence (benchmarks SERVE_SEQ)
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a described-device compile can be written to it but not read
    back, so a later run would warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else the compiler logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.mark.parametrize("width", [1536, 2560, 5120])
@pytest.mark.parametrize("direction", ["quantize", "dequantize"])
def test_boundary_quant_compiles_to_tpu_kernel(one_chip, direction, width):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if direction == "quantize":
        lowered = ops.quantize.lower(sds((ROWS, width), jnp.bfloat16))
    else:
        lowered = ops.dequantize.lower(sds((ROWS, width), jnp.int8),
                                       sds((ROWS, 1), jnp.float32))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_full_width_two_stage_stablelm_fits_one_chip(one_chip):
    """stablelm-3b at its published width, split in two stages the way the
    serving path splits it, compiled at batch 8 x seq 256: the shared bf16
    parameters plus both programs' temporaries and outputs fit 16 GB."""
    seq, batch = 256, 8
    cfg = get_config("stablelm-3b")
    prof = profile_model(ModelSpec(arch="stablelm-3b", seq_len=seq),
                         ClusterSpec(counts={"tpu-hi": 1, "tpu-lo": 8}))
    n = prof.n_blocks
    model, stages = split_stages(
        cfg, [(0, n // 2), (n // 2, n)],
        layer_block_map_from_profile(prof, cfg.n_layers))

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip,
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    carry = on_chip(jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    mems = []
    for stage in stages:
        compiled = jax.jit(stage).lower(params, carry).compile()
        mems.append(compiled.memory_analysis())
        carry = on_chip(jax.eval_shape(stage, params, carry))
    assert carry.shape == (batch, seq, cfg.padded_vocab)
    # the stages share one parameter tree, so its bytes count once
    resident = (max(m.argument_size_in_bytes for m in mems)
                + sum(m.temp_size_in_bytes + m.output_size_in_bytes
                      for m in mems))
    assert resident < V5E_HBM_BYTES, [
        (m.argument_size_in_bytes, m.temp_size_in_bytes,
         m.output_size_in_bytes) for m in mems]


def _entry_slices_of_layer_params(hlo: str) -> list[str]:
    """The slice instructions (`slice`, its asynchronous `slice-start`, or
    `dynamic-slice`) of the compiled entry computation whose operand is a
    `params['layers']` leaf, directly or through copies (a small leaf may
    first be copied to on-chip memory)."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    instr = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)"
                       r"\(([^()]*)\)")
    layer_bufs, slices = set(), []
    for line in entry.splitlines():
        m = instr.match(line)
        if m is None:
            continue
        name, op, operands = m[1], m[2], re.findall(r"%([\w.\-]+)", m[3])
        if op == "parameter" and "params[\\'layers\\']" in line:
            layer_bufs.add(name)
        elif op in ("copy", "copy-start", "copy-done", "bitcast") and (
                operands and operands[0] in layer_bufs):
            layer_bufs.add(name)
        elif op in ("slice", "slice-start", "dynamic-slice") and (
                operands and operands[0] in layer_bufs):
            slices.append(line.strip()[:160])
    return slices


def test_stage_programs_read_layer_range_in_place(one_chip):
    """stablelm-3b at its published width, cut like the benchmark's cell
    (stage 0 = embedding and layers 0-5, stage 1 = layers 5-32 and the
    head), compiled at batch 8 x seq 256: neither stage copies its layer
    range out of the bound parameters before its scan, so no entry `slice`
    reads a layer leaf and the temporaries stay below one layer's weights
    (a range copied before the scan takes its whole size in temporaries)."""
    seq, batch = 256, 8
    cfg = get_config("stablelm-3b")
    prof = profile_model(ModelSpec(arch="stablelm-3b", seq_len=seq,
                                   n_blocks=10),
                         ClusterSpec(counts={"tpu-lo": 1, "tpu-hi": 1}))
    lbm = layer_block_map_from_profile(prof, cfg.n_layers)
    assert (lbm[0][0], lbm[1][1], lbm[2][0], lbm[-1][1]) == (0, 5, 5, 32)
    model, stages = split_stages(cfg, [(0, 2), (2, prof.n_blocks)], lbm)

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip,
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    layer_bytes = sum(a.size // a.shape[0] * a.dtype.itemsize
                      for a in jax.tree.leaves(params["layers"]))
    assert layer_bytes == 79_303_680 * 2
    carries = [on_chip(jax.ShapeDtypeStruct((batch, seq), jnp.int32)),
               on_chip(jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                            jnp.bfloat16))]
    for stage, carry in zip(stages, carries):
        compiled = jax.jit(stage).lower(params, carry).compile()
        assert _entry_slices_of_layer_params(compiled.as_text()) == [], \
            stage.__name__
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < layer_bytes, (stage.__name__, temp)
