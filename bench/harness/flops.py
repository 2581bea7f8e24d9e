"""Operations and bytes that each kind of work needs, from the shapes in
the configuration file: the yardstick of every roofline share and of
``mfu``.  Work is counted as the algorithm needs it (live tokens, causal
attention), never as a kernel's grid pads it.
"""
from __future__ import annotations

import json
import os
from typing import Dict

BF16 = 2
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def matmul_flops_per_token(m: Dict) -> float:
    """Forward matmul FLOPs of one token: every layer matrix and the
    output head (the embedding lookup is no matmul)."""
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    h, g, n = (m["num_attention_heads"], m["num_key_value_heads"],
               m["head_dim"])
    per_layer = d * h * n + 2 * d * g * n + h * n * d + 3 * d * f
    return 2.0 * (m["num_hidden_layers"] * per_layer + d * v)


def kv_bytes_per_token(m: Dict) -> float:
    """Bytes of keys and values one context token holds over all layers."""
    return (2.0 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * BF16)


def attention_flops(m: Dict, cached: int, new: int) -> float:
    """QK^T and PV FLOPs of ``new`` causal query tokens that follow
    ``cached`` context tokens (each attends to itself and all before)."""
    ctx = new * cached + new * (new + 1) / 2.0
    return (4.0 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * ctx)


def attention_bytes(m: Dict, cached: int, new: int) -> float:
    """Least bytes attention moves for ``new`` query tokens after ``cached``
    context: each query and output once, each key and value once."""
    L, h, g, n = (m["num_hidden_layers"], m["num_attention_heads"],
                  m["num_key_value_heads"], m["head_dim"])
    return L * n * BF16 * (2.0 * h * new + 2.0 * g * (cached + new))


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
