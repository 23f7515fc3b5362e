"""The program's model configuration, built from a benchmark config file.

The file states the model as it is run, under the Hugging Face key names
of its published ``config.json``; ``arch`` names the registered
configuration of ``repro.configs`` whose code runs it. Every stated key
replaces the registered value, so the program runs exactly what the file
says, and a key the program cannot honour is refused.
"""
from __future__ import annotations

import dataclasses

#: file key -> ``repro.configs.base.ModelConfig`` field
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def program_config(config: dict):
    """The ``ModelConfig`` that runs ``config``."""
    from repro.configs import get_config
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{config['name']}: only SwiGLU (silu) MLPs run")
    for key in ("attention_bias", "mlp_bias"):
        if config.get(key):
            raise ValueError(f"{config['name']}: {key} true does not run: "
                             f"the program has biases on q, k and v only "
                             f"(qkv_bias)")
    base = get_config(config["arch"])
    cfg = dataclasses.replace(
        base, name=config["name"],
        **{field: config[key] for key, field in FIELDS.items()})
    if cfg.block_pattern != ("attn",) or cfg.prelude or cfg.moe \
            or cfg.norm != "rmsnorm" or not cfg.gated_mlp or cfg.qk_norm \
            or cfg.post_norm or cfg.softcap or cfg.scale_embeddings:
        raise ValueError(f"{config['name']}: {config['arch']} is not the "
                         f"dense Llama-style block the reference computes")
    return cfg.validate()


def bytes_limit(device) -> float | None:
    """The memory the runtime lets one chip's buffers use (``None``
    where the backend reports none, as the CPU's does)."""
    stats = device.memory_stats() or {}
    return float(stats["bytes_limit"]) if "bytes_limit" in stats else None
