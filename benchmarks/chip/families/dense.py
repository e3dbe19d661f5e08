"""The dense decoder family: grouped-query attention with rotary
embedding, SwiGLU, RMSNorm and an untied head, as `reference.py` computes
it and `costs.py` counts it.  This module only names those functions."""

import costs
import reference

# `model` key -> the program's ModelConfig attribute
KEYS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
        "kv_heads": "kv_heads", "head_dim": "hd", "d_ff": "d_ff",
        "vocab": "vocab", "padded_vocab": "padded_vocab",
        "rope_theta": "rope_theta", "norm_eps": "norm_eps"}

# published config key -> `model` key
PUBLISHED = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
             "layer_norm_eps": "norm_eps"}

# a dense config that gives no head width has heads of hidden / heads
CONVENTIONS = {"head_dim": lambda pub: (pub["hidden_size"]
                                        // pub["num_attention_heads"])}


def check(cfg) -> bool:
    return (cfg.family == "dense" and not cfg.qkv_bias and not cfg.qk_norm
            and not cfg.tie_embeddings)


weights = reference.weights
logits = reference.logits
request_flops = costs.request_flops
stage_cost = costs.stage_cost
