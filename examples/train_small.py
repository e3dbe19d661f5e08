"""Train a ~100M-parameter qwen2-family model for a few hundred steps with
the full production stack: deterministic data pipeline, AdamW + remat +
grad accumulation, async sharded checkpoints, and elastic restart.

    PYTHONPATH=src python examples/train_small.py [--steps 300] [--fail-at 120]
"""

import argparse
import sys
import tempfile
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.api.compilation import enable_compile_cache
from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.models.common import count_params
from repro.models.model_zoo import build_model
from repro.training import AdamWConfig, init_opt_state, make_train_step
from repro.training.elastic import ElasticConfig, FailureInjector, run_elastic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a node failure at this step (tests recovery)")
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config("qwen2-1.5b").reduced(
        n_layers=args.layers, d_model=args.dim, d_ff=args.dim * 4,
        n_heads=8, kv_heads=2, vocab=8192, head_dim=args.dim // 8,
    )
    model = build_model(cfg)
    n_params = count_params(model.defs)
    print(f"model: {cfg.name}-reduced  params={n_params/1e6:.1f}M")

    opt_cfg = AdamWConfig(lr=1e-3)
    step_fn = jax.jit(make_train_step(model, opt_cfg, remat=True, accum_steps=2),
                      donate_argnums=(0, 1))
    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=128, global_batch=8)

    def make_state():
        params = model.init(jax.random.PRNGKey(0))
        return {"params": params, "opt": init_opt_state(params, opt_cfg)}

    def train_step(state, batch):
        params, opt, metrics = step_fn(state["params"], state["opt"], batch)
        return {"params": params, "opt": opt}, metrics

    def batch_for(step):
        return jax.tree.map(jnp.asarray, pipe.batch_for(step))

    ckpt_dir = tempfile.mkdtemp(prefix="train_small_")
    fail = FailureInjector({args.fail_at} if args.fail_at else set())
    cfg_e = ElasticConfig(ckpt_dir=ckpt_dir, ckpt_every=50)
    t0 = time.perf_counter()
    state, stats = run_elastic(make_state, train_step, batch_for, args.steps,
                               cfg_e, fail)
    wall = time.perf_counter() - t0
    losses = stats["losses"]
    k = max(1, len(losses) // 10)
    print(f"steps={args.steps} wall={wall:.1f}s restarts={stats['restarts']} "
          f"ckpt={ckpt_dir}")
    print(f"loss: first10={sum(losses[:k])/k:.3f} "
          f"last10={sum(losses[-k:])/k:.3f}")
    assert sum(losses[-k:]) / k < sum(losses[:k]) / k, "loss did not decrease"
    print("OK — loss decreased")


if __name__ == "__main__":
    enable_compile_cache()
    main()
