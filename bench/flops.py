"""Model FLOPs of a dense decoder transformer, from its configuration.

Counts the multiply-adds (2 FLOPs each) that the model's mathematics
needs, and nothing a particular implementation adds: no padding, no
masked-out half of causal attention, no recomputation. Norms, rotary
embeddings, softmax and the embedding lookup are left out (each is
under 0.1 % of the total at the widths benchmarked here), so a share of
the peak read from these counts is never inflated.

The configuration is the benchmark's own JSON (Hugging Face key names).
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peak(device_kind: str, what: str = "bf16_flops_per_s") -> float:
    """One chip's published peak (``bench/peaks.json``); a device that is
    not in the table is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS} with its source")
    return float(table[device_kind][what])


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int]:
    d = int(cfg["hidden_size"])
    hd = int(cfg["head_dim"])
    q = int(cfg["num_attention_heads"]) * hd
    kv = int(cfg["num_key_value_heads"]) * hd
    return d, q, kv, int(cfg["intermediate_size"]), int(cfg["vocab_size"]), \
        int(cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights one layer multiplies each token by: q, k, v, o and the
    gated MLP's three matrices."""
    d, q, kv, f, _, _ = _dims(cfg)
    return d * q + 2 * d * kv + q * d + 3 * d * f


def _attention(cfg: dict, keys: float) -> float:
    """QK^T and PV of one query over ``keys`` positions, all layers."""
    _, q, _, _, _, layers = _dims(cfg)
    return 4.0 * layers * q * keys


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward pass per token of a causal sequence of ``seq`` tokens with
    logits at every position (training): position ``i`` attends to
    ``i + 1`` keys, ``(seq + 1) / 2`` on average."""
    d, _, _, _, v, layers = _dims(cfg)
    return (2.0 * (layers * layer_matmul_params(cfg) + d * v)
            + _attention(cfg, (seq + 1) / 2.0))


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward (twice the forward's multiply-adds)."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt's prefill: every position through the layers, the
    output head at the last position only."""
    d, _, _, _, v, layers = _dims(cfg)
    n = int(prompt_len)
    return (2.0 * layers * layer_matmul_params(cfg) * n
            + _attention(cfg, n * (n + 1) / 2.0) + 2.0 * d * v)


def decode_flops(cfg: dict, cached: int) -> float:
    """One decoded token whose cache holds ``cached`` earlier positions:
    it attends to those and to itself."""
    d, _, _, _, v, layers = _dims(cfg)
    return (2.0 * (layers * layer_matmul_params(cfg) + d * v)
            + _attention(cfg, cached + 1))
