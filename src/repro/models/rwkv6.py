"""RWKV-6 "Finch" block: data-dependent-decay WKV recurrence + channel mix.

Reference path: the chunked-parallel WKV evaluation below (numerically stable:
all decay products are <= 1).  The TPU hot path is the Pallas kernel in
``repro.kernels.rwkv6_scan`` validated against :func:`wkv6_chunked_ref`.

Recurrence (per head, state S in R^{N_k x N_v}):
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
with w_t = exp(-exp(w0 + tanh(x_w A) B)) data-dependent per channel.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.context import RunContext
from repro.models.spec import ParamSpec

_LORA_RANK = 64
_CHUNK = 64


def rwkv_time_specs(cfg: ModelConfig):
    d, h, n = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "mu": ParamSpec((5, d), (None, "embed"), jnp.float32, init="zeros"),
        "w0": ParamSpec((d,), ("embed",), jnp.float32, init="zeros"),
        "wA": ParamSpec((d, _LORA_RANK), ("embed", "rank")),
        "wB": ParamSpec((_LORA_RANK, d), ("rank", "embed"), fan_in=_LORA_RANK),
        "u": ParamSpec((h, n), ("heads", "head_dim"), jnp.float32,
                       init="zeros"),
        "wr": ParamSpec((d, h, n), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, h, n), ("embed", "heads", "head_dim")),
        "wv": ParamSpec((d, h, n), ("embed", "heads", "head_dim")),
        "wg": ParamSpec((d, h, n), ("embed", "heads", "head_dim")),
        "gn_scale": ParamSpec((h, n), ("heads", "head_dim"), init="ones"),
        "gn_bias": ParamSpec((h, n), ("heads", "head_dim"), init="zeros"),
        "wo": ParamSpec((h, n, d), ("heads", "head_dim", "embed"),
                        fan_in=h * n),
    }


def rwkv_channel_specs(cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mu_k": ParamSpec((d,), ("embed",), jnp.float32, init="zeros"),
        "mu_r": ParamSpec((d,), ("embed",), jnp.float32, init="zeros"),
        "wk": ParamSpec((d, f), ("embed", "mlp")),
        "wv": ParamSpec((f, d), ("mlp", "embed"), fan_in=f),
        "wr": ParamSpec((d, d), ("embed", "embed_out")),
    }


def _token_shift(x: jax.Array, prev: Optional[jax.Array]) -> jax.Array:
    """x_{t-1} with the carried last token (or zeros) at t=0."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    else:
        prev = prev[:, None].astype(x.dtype)
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def wkv6_chunked_ref(r, k, v, w, u, s0, chunk: int = _CHUNK,
                     unroll: bool = False):
    """Chunked-parallel WKV. r,k,v,w: (B,S,H,N) — w is the decay in (0,1].

    Returns y: (B,S,H,N), s_final: (B,H,N,N) fp32.
    All decay factors appearing in products are <=1 => numerically stable.
    """
    b, s, h, n = r.shape
    c = min(chunk, s)
    assert s % c == 0, (s, c)
    m = s // c
    f32 = jnp.float32
    rs, ks, vs, ws = (a.astype(f32).reshape(b, m, c, h, n) for a in (r, k, v, w))
    lw = jnp.log(jnp.maximum(ws, 1e-30))
    cum_incl = jnp.cumsum(lw, axis=2)                 # log prod_{1..t}
    cum_excl = cum_incl - lw                          # log prod_{1..t-1}
    total = jnp.exp(cum_incl[:, :, -1])               # (B,M,H,N)

    # ---- intra-chunk: scan over the C positions, vectorized over chunks ----
    def step(S, xs):
        r_t, k_t, v_t, w_t = xs                       # (B,M,H,N)
        bonus = jnp.einsum("bmhk,hk,bmhk->bmh", r_t, u.astype(f32), k_t)
        y = jnp.einsum("bmhk,bmhkv->bmhv", r_t, S) + bonus[..., None] * v_t
        S = w_t[..., None] * S + k_t[..., None] * v_t[..., None, :]
        return S, y

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (rs, ks, vs, ws))
    # analysis mode unrolls so cost_analysis sees all C steps (the inter-
    # chunk scan is ~2% of the FLOPs and stays rolled)
    delta, y_intra = jax.lax.scan(step, jnp.zeros((b, m, h, n, n), f32), xs,
                                  unroll=c if unroll else 1)
    y_intra = jnp.moveaxis(y_intra, 0, 2)             # (B,M,C,H,N)

    # ---- inter-chunk: propagate state across chunks (M sequential steps) ----
    def step2(S, xs):
        tot, dlt = xs                                 # (B,H,N), (B,H,N,N)
        return tot[..., None] * S + dlt, S

    s0 = jnp.zeros((b, h, n, n), f32) if s0 is None else s0.astype(f32)
    s_final, s_prefix = jax.lax.scan(
        step2, s0, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(delta, 1, 0)))
    s_prefix = jnp.moveaxis(s_prefix, 0, 1)           # (B,M,H,N,N)

    # ---- prefix-state contribution ----
    rq = rs * jnp.exp(cum_excl)                       # decays <= 1
    y = y_intra + jnp.einsum("bmchk,bmhkv->bmchv", rq, s_prefix)
    return y.reshape(b, s, h, n).astype(r.dtype), s_final


def wkv6_step(r, k, v, w, u, s0):
    """Single decode step. r,k,v,w: (B,1,H,N); s0: (B,H,N,N) fp32."""
    f32 = jnp.float32
    r_, k_, v_, w_ = (a.astype(f32)[:, 0] for a in (r, k, v, w))
    bonus = jnp.einsum("bhk,hk,bhk->bh", r_, u.astype(f32), k_)
    y = jnp.einsum("bhk,bhkv->bhv", r_, s0) + bonus[..., None] * v_
    s1 = w_[..., None] * s0 + k_[..., None] * v_[..., None, :]
    return y[:, None].astype(r.dtype), s1


def _group_norm(y: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    """Per-head layer norm. y: (B,S,H,N)."""
    f = y.astype(jnp.float32)
    mu = jnp.mean(f, -1, keepdims=True)
    var = jnp.var(f, -1, keepdims=True)
    out = (f - mu) * jax.lax.rsqrt(var + 1e-5)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(y.dtype)


def rwkv_time_apply(params: dict, x: jax.Array, cfg: ModelConfig,
                    ctx: RunContext, cache: Optional[dict], mode: str):
    """Time-mix. cache = {"prev": (B,D), "s": (B,H,N,N) f32}."""
    b, s, d = x.shape
    h, n = cfg.n_heads, cfg.head_dim
    prev = cache["prev"] if cache is not None else None
    xp = _token_shift(x, prev) if mode != "decode" else (
        prev[:, None].astype(x.dtype) if prev is not None
        else jnp.zeros_like(x))
    mu = params["mu"].astype(x.dtype)
    mixed = [x + (xp - x) * mu[i] for i in range(5)]  # r,k,v,g,w
    xr, xk, xv, xg, xw = mixed

    def proj(inp, wname):
        return jnp.einsum("bsd,dhn->bshn", inp, params[wname],
                          preferred_element_type=jnp.float32).astype(x.dtype)

    r, k, v = proj(xr, "wr"), proj(xk, "wk"), proj(xv, "wv")
    g = jax.nn.silu(proj(xg, "wg").astype(jnp.float32)).astype(x.dtype)
    lora = jnp.einsum("bsr,rd->bsd",
                      jnp.tanh(jnp.einsum("bsd,dr->bsr",
                                          xw.astype(jnp.float32),
                                          params["wA"].astype(jnp.float32))),
                      params["wB"].astype(jnp.float32))
    w = jnp.exp(-jnp.exp(params["w0"].astype(jnp.float32) + lora))
    w = w.reshape(b, s, h, n)

    s0 = cache["s"] if cache is not None else None
    if mode == "decode":
        y, s_new = wkv6_step(r, k, v, w, params["u"], s0)
    elif ctx.kernels:
        from repro.kernels import ops as kops
        y, s_new = kops.rwkv6_scan(r, k, v, w, params["u"], s0=s0,
                                   interpret=ctx.interpret)
    else:
        y, s_new = wkv6_chunked_ref(r, k, v, w, params["u"], s0,
                                    unroll=ctx.scan_unroll)

    y = _group_norm(y, params["gn_scale"], params["gn_bias"]) * g
    out = jnp.einsum("bshn,hnd->bsd", y, params["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    new_cache = None
    if cache is not None or mode == "prefill":
        new_cache = {"prev": x[:, -1].astype(jnp.float32), "s": s_new}
    return out, new_cache


def rwkv_channel_apply(params: dict, x: jax.Array, cfg: ModelConfig,
                       cache: Optional[dict], mode: str):
    """Channel-mix. cache = {"prev": (B,D)}."""
    prev = cache["prev"] if cache is not None else None
    xp = _token_shift(x, prev) if mode != "decode" else (
        prev[:, None].astype(x.dtype) if prev is not None
        else jnp.zeros_like(x))
    mu_k = params["mu_k"].astype(x.dtype)
    mu_r = params["mu_r"].astype(x.dtype)
    xk = x + (xp - x) * mu_k
    xr = x + (xp - x) * mu_r
    kk = jnp.einsum("bsd,df->bsf", xk, params["wk"],
                    preferred_element_type=jnp.float32)
    kk = jnp.square(jax.nn.relu(kk)).astype(x.dtype)
    vv = jnp.einsum("bsf,fd->bsd", kk, params["wv"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    rr = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", xr, params["wr"],
                   preferred_element_type=jnp.float32))
    out = (rr * vv.astype(jnp.float32)).astype(x.dtype)
    new_cache = None
    if cache is not None or mode == "prefill":
        new_cache = {"prev": x[:, -1].astype(jnp.float32)}
    return out, new_cache


def init_rwkv_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    h, n, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    return {
        "tm": {"prev": jnp.zeros((batch, d), jnp.float32),
               "s": jnp.zeros((batch, h, n, n), jnp.float32)},
        "cm": {"prev": jnp.zeros((batch, d), jnp.float32)},
    }
