"""The plain reference against the program, at toy size on the CPU: the
seeded weights are the program's bit for bit, and the float32 forward pass
is the program's math (the program run in float32)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference


def test_weights_are_the_programs_seeded_init(conf):
    from repro.models.model_zoo import build_model

    cfg = harness.program_model(conf)
    seed = harness.derive(2**31 + 9, "weights")
    prog = build_model(cfg).init(jax.random.PRNGKey(seed))
    ours = reference.weights(conf["model"], seed)
    flat = {"embed": prog["embed"], "final_norm": prog["final_norm"],
            "head": prog["head"]}
    for name in reference._LAYER_LEAVES:
        node = prog["layers"]
        for part in name.split("."):
            node = node[part]
        flat[name] = node
    assert sorted(flat) == sorted(ours)
    for name, arr in flat.items():
        assert arr.dtype == ours[name].dtype == jnp.bfloat16, name
        np.testing.assert_array_equal(np.asarray(arr, np.float32),
                                      np.asarray(ours[name], np.float32), name)


def test_float32_reference_is_the_programs_math(conf):
    from repro.models.model_zoo import build_model

    cfg = dataclasses.replace(harness.program_model(conf), dtype=jnp.float32)
    w = reference.weights(conf["model"], 7)
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    params = {"embed": w32["embed"], "final_norm": w32["final_norm"],
              "head": w32["head"], "layers": {"attn": {}, "mlp": {}}}
    for name in reference._LAYER_LEAVES:
        group, _, leaf = name.rpartition(".")
        (params["layers"][group] if group else params["layers"])[
            leaf or name] = w32[name]
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, conf["seq_len"]), 0,
                              conf["model"]["vocab"])
    with jax.default_matmul_precision("highest"):
        prog = build_model(cfg).forward(params, {"tokens": toks})
    ref = reference.logits(w, conf["model"], toks)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(prog - ref))) <= 1e-4 * scale


@pytest.mark.parametrize("precision", ["bf16", "fp8"])
def test_lower_precisions_move_the_logits(conf, precision):
    w = reference.weights(conf["model"], 3)
    toks = jnp.arange(2 * conf["seq_len"]).reshape(2, -1) % 97
    ref = reference.logits(w, conf["model"], toks)
    low = reference.logits(w, conf["model"], toks, precision=precision)
    rel = float(jnp.max(jnp.abs(low - ref)) / jnp.max(jnp.abs(ref)))
    assert 1e-4 < rel < 1.0
