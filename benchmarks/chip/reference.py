"""Plain reference of the served dense decoder, in `jax.numpy`.

It imports nothing of the program.  The math is the one the program serves
(and PERF.md lists where that departs from the published models): RMSNorm
(eps from the configuration),
rotary embedding on the two halves of each head over the full head width,
causal softmax attention with grouped key/value heads, SwiGLU, a final
RMSNorm and an untied head over the padded vocabulary.

`weights` makes the served weights from the seed with the seeded init the
program documents (`ServeConfig.seed` -> `PRNGKey(seed)`, one key per leaf
in the sorted order of the parameter tree, normal x 1/sqrt(leading dim),
0.02 for the embedding, ones for norm scales, cast to bf16), in one jitted
call on the device.  The forward pass runs layer by layer, each layer's
weights upcast to float32 only while it runs, at `Precision.HIGHEST`.

`precision="fp8"` is the control: the same pass with both operands of every
matrix product rounded to float8 e4m3 under a per-tensor scale, the step
below the bf16 the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0

# the stacked layer leaves, in the order a pytree of them flattens
_LAYER_LEAVES = ("attn.wk", "attn.wo", "attn.wq", "attn.wv", "attn_norm",
                 "mlp.down", "mlp.gate", "mlp.up", "mlp_norm")


def _shapes(m: dict) -> dict:
    d, h, kh, hd, ff = (m["d_model"], m["n_heads"], m["kv_heads"],
                        m["head_dim"], m["d_ff"])
    n, vp = m["n_layers"], m["padded_vocab"]
    return {
        "embed": (vp, d), "final_norm": (d,), "head": (d, vp),
        "attn.wk": (n, d, kh * hd), "attn.wo": (n, h * hd, d),
        "attn.wq": (n, d, h * hd), "attn.wv": (n, d, kh * hd),
        "attn_norm": (n, d), "mlp.down": (n, ff, d), "mlp.gate": (n, d, ff),
        "mlp.up": (n, d, ff), "mlp_norm": (n, d),
    }


def _make(m: dict, seed: int) -> dict:
    shapes = _shapes(m)
    order = ("embed", "final_norm", "head") + _LAYER_LEAVES
    keys = jax.random.split(jax.random.PRNGKey(seed), len(order))
    out = {}
    for name, key in zip(order, keys):
        shape = shapes[name]
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        scale = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[0])
        # the barrier keeps the draw apart from the scaling, as the program's
        # leaf-by-leaf init computes it, so the roundings match bit for bit
        draw = jax.lax.optimization_barrier(
            jax.random.normal(key, shape, jnp.float32))
        out[name] = (draw * scale).astype(jnp.bfloat16)
    return out


def weights(m: dict, seed: int) -> dict:
    """The served bf16 weights for `seed`, made in one jitted call."""
    key = tuple(sorted((k, v) for k, v in m.items()
                       if isinstance(v, (int, float))))
    return _make_jit(key, seed)


@functools.partial(jax.jit, static_argnums=0)
def _make_jit(mkey: tuple, seed):
    return _make(dict(mkey), seed)


def _mm(precision: str):
    if precision == "f32":
        def mm(spec, a, b):
            return jnp.einsum(spec, a, b, precision=HIGHEST)
    elif precision == "bf16":
        def mm(spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
    elif precision == "fp8":
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

        def mm(spec, a, b):
            return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer(x, w, *, m, precision):
    mm = _mm(precision)
    m = dict(m)
    b, s, _ = x.shape
    h, kh, hd = m["n_heads"], m["kv_heads"], m["head_dim"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    a = _rms(x, w["attn_norm"], m["norm_eps"])
    q = mm("bsd,dh->bsh", a, w["attn.wq"]).reshape(b, s, h, hd)
    k = mm("bsd,dh->bsh", a, w["attn.wk"]).reshape(b, s, kh, hd)
    v = mm("bsd,dh->bsh", a, w["attn.wv"]).reshape(b, s, kh, hd)
    q = _rope(q, m["rope_theta"])
    k = _rope(k, m["rope_theta"])
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    sc = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v).reshape(b, s, h * hd)
    x = x + mm("bsh,hd->bsd", o, w["attn.wo"])
    a = _rms(x, w["mlp_norm"], m["norm_eps"])
    g = mm("bsd,df->bsf", a, w["mlp.gate"])
    u = mm("bsd,df->bsf", a, w["mlp.up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["mlp.down"])


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _head(x, final_norm, head, *, m, precision):
    x = _rms(x, final_norm.astype(jnp.float32), dict(m)["norm_eps"])
    return _mm(precision)("bsd,dv->bsv", x, head.astype(jnp.float32))


def logits(w: dict, m: dict, tokens, precision: str = "f32") -> jax.Array:
    """(rows, seq, padded_vocab) float32 logits of `tokens` (rows, seq)."""
    mk = tuple(sorted((k, v) for k, v in m.items()
                      if isinstance(v, (int, float))))
    x = w["embed"][tokens].astype(jnp.float32)
    for i in range(m["n_layers"]):
        lw = {k: w[k][i] for k in _LAYER_LEAVES}
        x = _layer(x, lw, m=mk, precision=precision)
    return _head(x, w["final_norm"], w["head"], m=mk, precision=precision)


@jax.jit
def top1_gaps(ref, chosen):
    """Per position: how far the reference's logit of the `chosen` token lies
    below the reference's best, in units of that position's logit spread."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    return (best - got) / jnp.std(ref, axis=-1)
