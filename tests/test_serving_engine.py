"""Serving engine: stage-split execution equals the whole-model forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import PipelinePlan, StagePlan
from repro.models import transformer as tfm
from repro.models.common import NO_SHARDING
from repro.serving.engine import build_engine, split_stages

KEY = jax.random.PRNGKey(3)


def _layer_block_map(n_layers, n_blocks):
    """block 0 = embed, blocks 1..n-2 = layer groups, last = head."""
    per = max(1, n_layers // (n_blocks - 2))
    blocks = [(0, 0)]  # embed: no layers
    start = 0
    while start < n_layers:
        end = min(n_layers, start + per)
        blocks.append((start, end))
        start = end
    blocks.append((n_layers, n_layers))  # head
    return blocks


@pytest.mark.parametrize("cuts", [(2,), (2, 4)],
                         ids=["two_stages", "three_stages"])
def test_stage_split_matches_full_forward(cuts):
    """Chained stages equal the whole forward; with three stages the middle
    one neither embeds nor unembeds and starts past layer 0, so its layer
    range is offset at both ends."""
    cfg = get_config("stablelm-3b").reduced(n_layers=4)
    lbm = _layer_block_map(cfg.n_layers, 5)
    n = len(lbm)
    bounds = (0, *cuts, n)
    model, stages = split_stages(cfg, list(zip(bounds, bounds[1:])), lbm)
    params = model.init(KEY)
    tokens = jnp.arange(2 * 12, dtype=jnp.int32).reshape(2, 12) % cfg.vocab
    full = model.forward(params, {"tokens": tokens})
    out = tokens
    for stage in stages:
        out = stage(params, out)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(full, np.float32), atol=2e-2, rtol=2e-2)


def test_middle_stage_bit_identical_to_sliced_scan():
    """A middle stage, which indexes its layers inside the scan, gives the
    same bits as scanning over a slice of the stacked layer parameters."""
    cfg = get_config("stablelm-3b").reduced(n_layers=4)
    lbm = _layer_block_map(cfg.n_layers, 5)
    n = len(lbm)
    model, stages = split_stages(cfg, [(0, 2), (2, 4), (4, n)], lbm)
    lstart, lend = lbm[2][0], lbm[3][1]
    assert 0 < lstart < lend < cfg.n_layers
    params = model.init(KEY)
    tokens = jnp.arange(2 * 12, dtype=jnp.int32).reshape(2, 12) % cfg.vocab
    h = jax.jit(stages[0])(params, tokens)

    def sliced_scan(params, x):
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(x, lp):
            x, _ = tfm.layer_full(cfg, NO_SHARDING, lp, x, positions)
            return x, None

        lslice = jax.tree.map(lambda a: a[lstart:lend], params["layers"])
        return jax.lax.scan(body, x, lslice)[0]

    np.testing.assert_array_equal(
        np.asarray(jax.jit(stages[1])(params, h), np.float32),
        np.asarray(jax.jit(sliced_scan)(params, h), np.float32))


def test_stage_functions_named_by_block_range():
    """Each stage program is named for its block range, so its compiled
    module (and its device-trace events) read `jit_stage_b{i}_{j}`."""
    cfg = get_config("stablelm-3b").reduced(n_layers=4)
    lbm = _layer_block_map(cfg.n_layers, 5)
    n = len(lbm)
    model, stages = split_stages(cfg, [(0, 2), (2, n)], lbm)
    assert [f.__name__ for f in stages] == ["stage_b0_2", f"stage_b2_{n}"]
    params = jax.eval_shape(model.init, KEY)
    tokens = jax.ShapeDtypeStruct((2, 12), jnp.int32)
    text = jax.jit(stages[0]).lower(params, tokens).as_text()
    assert text.startswith("module @jit_stage_b0_2 ")


def test_engine_runs_pipeline_plan():
    cfg = get_config("stablelm-3b").reduced(n_layers=4)
    lbm = _layer_block_map(cfg.n_layers, 5)
    n = len(lbm)
    plan = PipelinePlan(
        model_name=cfg.name, batch_size=2,
        stages=(
            StagePlan(0, 2, "tpu-lo", 1, 2, 0.01),
            StagePlan(2, n, "tpu-hi", 1, 1, 0.01),
        ),
        xfer_latency_s=(0.001,),
    )
    engine = build_engine(cfg, plan, lbm, KEY)
    tokens = jnp.ones((2, 16), jnp.int32)
    out = engine.infer(tokens)
    assert out.shape == (2, 16, cfg.padded_vocab)
    assert not np.isnan(np.asarray(out, np.float32)).any()


def test_boundary_quantization_small_error():
    """int8 boundary quantization must not meaningfully perturb logits
    (paper reports <=0.01% accuracy change for fp16)."""
    cfg = get_config("stablelm-3b").reduced(n_layers=4)
    lbm = _layer_block_map(cfg.n_layers, 5)
    n = len(lbm)
    model, stages = split_stages(cfg, [(0, 2), (2, n)], lbm)
    params = model.init(KEY)
    tokens = jnp.arange(2 * 12, dtype=jnp.int32).reshape(2, 12) % cfg.vocab
    h = stages[0](params, tokens)
    from repro.kernels.boundary_quant import ops as bq

    q, s = bq.quantize(h)
    h2 = bq.dequantize(q, s, dtype=h.dtype)
    out_ref = np.asarray(stages[1](params, h), np.float32)
    out_q = np.asarray(stages[1](params, h2), np.float32)
    # logits barely move...
    err = np.abs(out_q - out_ref).max()
    assert err < 0.4, f"int8 boundary round-trip moved logits by {err}"
    # ...and the top-1 prediction is unchanged wherever the reference's
    # top-2 margin exceeds that numeric perturbation (the same decisive-
    # margin rule test_models uses: with a 24-position reduced model, one
    # near-tie flip is bf16 noise, not quantization damage)
    top2 = np.sort(out_ref, axis=-1)[..., -2:]
    decisive = (top2[..., 1] - top2[..., 0]) > 2 * err
    agree = out_ref.argmax(-1) == out_q.argmax(-1)
    assert decisive.any(), "reduced model produced no decisive positions"
    assert agree[decisive].all()
