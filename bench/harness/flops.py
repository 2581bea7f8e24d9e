"""Operations and bytes that each kind of work needs, from the shapes in
the configuration file: the yardstick of every roofline share and of
``mfu``.  The counts are the configuration's family's
(``families/<architecture>.py``, found by ``spec.family``); the chip's
peaks and the roofline bound are shared.
"""
from __future__ import annotations

import json
import os
from typing import Dict

from harness import spec

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
    """Forward matmul FLOPs of one token (the family's count)."""
    return spec.family(m).matmul_flops_per_token(m)


def kv_bytes_per_token(m: Dict) -> float:
    """Bytes of keys and values one context token holds (the family's)."""
    return spec.family(m).kv_bytes_per_token(m)


def attention_flops(m: Dict, cached: int, new: int) -> float:
    """Attention FLOPs of ``new`` query tokens after ``cached`` context
    (the family's count)."""
    return spec.family(m).attention_flops(m, cached, new)


def attention_bytes(m: Dict, cached: int, new: int) -> float:
    """Least bytes attention moves for ``new`` query tokens after ``cached``
    context (the family's count)."""
    return spec.family(m).attention_bytes(m, cached, new)


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
