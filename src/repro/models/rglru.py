"""RG-LRU recurrent block (Griffin / recurrentgemma).

Reference path: temporal depthwise conv + gated linear recurrence evaluated
with ``jax.lax.associative_scan`` (log-depth, XLA-native).  The TPU hot path
is the chunked Pallas kernel in ``repro.kernels.rglru_scan`` validated against
this implementation.

Block structure (Griffin, arXiv:2402.19427):
    y = W_out[ RG-LRU(conv1d(x W_x)) * gelu(x W_y) ]
    r_t = sigmoid(x_t W_a);  i_t = sigmoid(x_t W_i)
    a_t = exp(-c * softplus(Lambda) * r_t),  c = 8
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.context import RunContext
from repro.models.spec import ParamSpec

_C = 8.0


def rglru_specs(cfg: ModelConfig):
    d = cfg.d_model
    r = cfg.d_model                     # lru_width = d_model
    w = cfg.conv1d_width
    return {
        "wx": ParamSpec((d, r), ("embed", "lru")),
        "wy": ParamSpec((d, r), ("embed", "lru")),
        "conv_w": ParamSpec((w, r), (None, "lru"), fan_in=w),
        "conv_b": ParamSpec((r,), ("lru",), init="zeros"),
        "wa": ParamSpec((r, r), ("lru_in", "lru")),
        "ba": ParamSpec((r,), ("lru",), init="zeros"),
        "wi": ParamSpec((r, r), ("lru_in", "lru")),
        "bi": ParamSpec((r,), ("lru",), init="zeros"),
        "lam": ParamSpec((r,), ("lru",), init="ones"),
        "wout": ParamSpec((r, d), ("lru", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 carry: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: (B,S,R); w: (W,R); carry: (B,W-1,R)."""
    width = w.shape[0]
    if carry is None:
        carry = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([carry, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(width)) + b
    return y.astype(x.dtype), xp[:, -(width - 1):]


def _gates(xc: jax.Array, p: dict):
    """Returns (a, mult*i*xc) in fp32 — the linear-recurrence coefficients."""
    xf = xc.astype(jnp.float32)
    rg = jax.nn.sigmoid(xf @ p["wa"].astype(jnp.float32)
                        + p["ba"].astype(jnp.float32))
    ig = jax.nn.sigmoid(xf @ p["wi"].astype(jnp.float32)
                        + p["bi"].astype(jnp.float32))
    log_a = -_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * rg
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    return a, mult * ig * xf


def rglru_scan_ref(xc: jax.Array, p: dict,
                   h0: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence recurrence via associative scan. xc: (B,S,R)."""
    a, b = _gates(xc, p)
    if h0 is not None:
        # fold the carried state into the first step: h_1 = a_1 h_0 + b_1
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return ar * al, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(xc.dtype), h[:, -1].astype(jnp.float32)


def rglru_step(xc: jax.Array, p: dict, h0: jax.Array):
    """Single decode step. xc: (B,1,R); h0: (B,R) fp32."""
    a, b = _gates(xc, p)
    h = a[:, 0] * h0 + b[:, 0]
    return h[:, None].astype(xc.dtype), h


def rglru_block_apply(params: dict, x: jax.Array, cfg: ModelConfig,
                      ctx: RunContext, cache: Optional[dict], mode: str):
    """x: (B,S,D) -> (y, new_cache). cache = {"h": (B,R) f32, "conv": (B,W-1,R)}."""
    gate = jax.nn.gelu(
        jnp.einsum("bsd,dr->bsr", x, params["wy"],
                   preferred_element_type=jnp.float32)).astype(x.dtype)
    xb = jnp.einsum("bsd,dr->bsr", x, params["wx"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    conv_carry = cache["conv"] if cache is not None else None
    xc, new_conv = _causal_conv(xb, params["conv_w"], params["conv_b"],
                                conv_carry)
    if mode == "decode":
        h_seq, h_last = rglru_step(xc, params, cache["h"])
    elif ctx.kernels:
        from repro.kernels import ops as kops
        h0 = cache["h"] if cache is not None else None
        a, b = _gates(xc, params)
        h_seq, h_last = kops.rglru_scan(a, b, h0=h0,
                                        interpret=ctx.interpret)
        h_seq = h_seq.astype(xc.dtype)
    else:
        h0 = cache["h"] if cache is not None else None
        h_seq, h_last = rglru_scan_ref(xc, params, h0)
    y = jnp.einsum("bsr,rd->bsd", h_seq * gate, params["wout"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    new_cache = None
    if cache is not None or mode == "prefill":
        new_cache = {"h": h_last, "conv": new_conv}
    return y, new_cache


def _gates_tuple(xc, p):
    a, b = _gates(xc, p)
    return a, b


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    r, w = cfg.d_model, cfg.conv1d_width
    return {"h": jnp.zeros((batch, r), jnp.float32),
            "conv": jnp.zeros((batch, w - 1, r), dtype)}
