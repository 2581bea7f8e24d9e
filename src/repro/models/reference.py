"""Plain float32 reference forward pass for the dense decoder configs.

Straight-line ``jax.numpy`` at float32, with no kernels, cache, batching
or shared layer code: the oracle that the served path (bf16 weights,
Pallas kernels, paged KV) is held to, on the CPU in tests and at the
published widths on the chip (``chip_smoke.py``).  It reads the parameter
tree of :mod:`repro.models.model` and follows the blocks that
``models/blocks.py`` builds for a dense all-attention config: RMSNorm with
a ``1 + scale`` gain, rotate-half RoPE, grouped-query causal attention,
an optionally gated MLP, and tied or separate output embeddings.

Callers set ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs in bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
         "gelu": lambda x: jax.nn.gelu(x, approximate=True)}


def supports(cfg: ModelConfig) -> bool:
    """Dense, causal, windowless, text-only, one attention block kind."""
    return (cfg.family == "dense" and tuple(cfg.block_pattern) == ("attn",)
            and cfg.window is None and cfg.causal and cfg.frontend is None
            and not cfg.is_moe and cfg.norm_type == "rmsnorm")


def _rms_norm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + 1e-6) * (1.0 + scale)


def forward_logits(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    """tokens (S,) int32 -> logits (S, vocab) float32 of one sequence."""
    if not supports(cfg):
        raise NotImplementedError(f"no plain reference for {cfg.name}")
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s, d, n = tokens.shape[0], cfg.d_model, cfg.head_dim
    g = cfg.n_heads // cfg.n_kv_heads
    x = p["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * np.sqrt(d)
    half = n // 2
    inv_freq = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]

    def rope(t):                                  # (S, H, n)
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    act = _ACTS[cfg.mlp_act]

    def layer(x, lp):
        a = lp["attn"]
        h = _rms_norm(x, lp["norm1"]["scale"])
        q = jnp.einsum("sd,dhn->shn", h, a["wq"])
        k = jnp.einsum("sd,dhn->shn", h, a["wk"])
        v = jnp.einsum("sd,dhn->shn", h, a["wv"])
        if cfg.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = rope(q), rope(k)
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhn,khn->hqk", q, k) / np.sqrt(n)
        if cfg.logit_softcap is not None:
            sc = cfg.logit_softcap * jnp.tanh(sc / cfg.logit_softcap)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khn->qhn", pr, v)
        x = x + jnp.einsum("qhn,hnd->qd", o, a["wo"])
        f = lp["ffn"]
        h = _rms_norm(x, lp["norm2"]["scale"])
        u = h @ f["wi"]
        u = act(h @ f["wg"]) * u if cfg.mlp_gated else act(u)
        return x + u @ f["wo"], None

    x, _ = jax.lax.scan(layer, x, p["layers"]["stack"]["b0"])
    x = _rms_norm(x, p["final_norm"]["scale"])
    logits = x @ (p["embed"].T if cfg.tie_embeddings else p["head"])
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits
