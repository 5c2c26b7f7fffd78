"""Operations of a dense decoder LM, from its configuration's shapes.

Model FLOPs count the matrix products the algorithm needs, two per
multiply-add: forward and backward (3x forward) for training. The tied head
counts as a matmul, the embedding lookup does not. Recomputation (remat)
and the recipes' quantize-dequantize arithmetic do not count.
"""
from __future__ import annotations

from typing import Dict


def _dims(model: Dict):
    nh = model["num_attention_heads"]
    nkv = model.get("num_key_value_heads", nh)
    hd = model.get("head_dim") or model["hidden_size"] // nh
    return (model["num_hidden_layers"], model["hidden_size"],
            model["intermediate_size"], model["vocab_size"], nh, nkv, hd)


def param_counts(model: Dict) -> Dict[str, int]:
    """``total`` parameters and those in ``matmul`` weights."""
    L, d, f, V, nh, nkv, hd = _dims(model)
    arch = model["architecture"]
    layer_mm = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f
    layer_other = 2 * d
    if arch["qkv_bias"]:
        layer_other += (nh + 2 * nkv) * hd
    if arch["qk_norm"]:
        layer_other += 2 * hd
    head = V * d if model.get("tie_word_embeddings", True) else 2 * V * d
    matmul = L * layer_mm + V * d
    return {"matmul": matmul, "total": head + L * (layer_mm + layer_other) + d}


def attention_flops_per_token(model: Dict, seq: int) -> float:
    """Causal score and value products, forward only, averaged over the
    (seq + 1) / 2 keys a query sees."""
    L, _, _, _, nh, _, hd = _dims(model)
    return L * 4 * nh * hd * (seq + 1) / 2


def train_flops_per_token(model: Dict, seq: int) -> float:
    return 3 * (2 * param_counts(model)["matmul"]
                + attention_flops_per_token(model, seq))
