"""Pure-jnp oracles for every Pallas kernel (the allclose targets in tests).

These are *independent* straight-line implementations — deliberately naive —
so that kernel bugs can't hide behind shared code with the model reference
paths (which are themselves validated against these in tests).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window: Optional[int] = None,
                        softcap: Optional[float] = None):
    """q: (B,Hq,S,D); k,v: (B,Hkv,T,D) -> (B,Hq,S,D). Materializes scores."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (d ** -0.5)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(k.shape[2])[None, :]
    ok = jnp.ones((s, k.shape[2]), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    scores = jnp.where(ok, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def _pages_by_head(pool, block_tables):
    """(N, Hkv, bs, D) pool -> each slot's blocks in logical order,
    (B, Hkv, M*bs, D)."""
    pages = pool[block_tables]                           # (B, M, Hkv, bs, D)
    b, m, hkv, bs, d = pages.shape
    return jnp.swapaxes(pages, 1, 2).reshape(b, hkv, m * bs, d)


def paged_attention_ref(q, k_pool, v_pool, block_tables, context_lens, *,
                        softcap: Optional[float] = None):
    """Decode-time paged attention oracle (block-table gather, materialized).

    q: (B, Hq, D) one query per slot; k_pool, v_pool: (N, Hkv, bs, D) the
    shared head-major KV block pool; block_tables: (B, M) int32 physical
    block ids in logical order; context_lens: (B,) int32 tokens written per
    slot (the per-slot cursor + 1).  Returns (B, Hq, D).  Positions >= context_lens[i]
    (including every slot of an unused table entry) are masked out, so stale
    pool contents can never leak into a slot's output.

    This is the single oracle for *both* paged kernel grids — the per-head
    (B, Hq, M) kernel and the chunked (B, ceil(M/P)) kernel — because the
    grid only changes how often a KV block is staged, never the math;
    tests assert both against it (tests/test_kernels.py).
    """
    b, hq, d = q.shape
    hkv = k_pool.shape[1]
    g = hq // hkv
    k = _pages_by_head(k_pool, block_tables)             # (B, Hkv, M*bs, D)
    v = _pages_by_head(v_pool, block_tables)
    kk = jnp.repeat(k, g, axis=1)                        # (B, Hq, M*bs, D)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (d ** -0.5)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    ok = jnp.arange(k.shape[2])[None, :] < context_lens[:, None]   # (B, M*bs)
    scores = jnp.where(ok[:, None, :], scores, -1e30)
    # re-mask after softmax: for a live row this is exact (masked probs
    # underflow to 0.0), while a context_lens==0 row — where softmax
    # degrades to uniform over pure garbage — goes to all-zero output,
    # matching the kernel's zero accumulator
    probs = jax.nn.softmax(scores, axis=-1) * ok[:, None, :]
    out = jnp.einsum("bhk,bhkd->bhd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_prefill_ref(q, k_pool, v_pool, block_tables, pos0, n_live, *,
                      softcap: Optional[float] = None):
    """Chunked-prefill paged attention oracle (block-table gather).

    q: (B, Hq, C, D) one C-token suffix chunk per slot; k_pool, v_pool:
    (N, Hkv, bs, D) the shared head-major KV block pool (the chunk's own
    KV already scattered in); block_tables: (B, M) int32; pos0: (B,) int32
    absolute
    position of each chunk's first token; n_live: (B,) int32 live tokens
    per chunk (0..C).  Returns (B, Hq, C, D).  Chunk position t attends to
    key positions <= pos0 + t (resident prefix + intra-chunk causal); rows
    with t >= n_live — including every row of an n_live==0 slot — are
    re-masked to exact zero after the softmax, matching the kernel's
    zeroed accumulator for dead rows.
    """
    b, hq, c, d = q.shape
    hkv = k_pool.shape[1]
    g = hq // hkv
    k = _pages_by_head(k_pool, block_tables)             # (B, Hkv, M*bs, D)
    v = _pages_by_head(v_pool, block_tables)
    kk = jnp.repeat(k, g, axis=1)                        # (B, Hq, M*bs, D)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhcd,bhkd->bhck", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (d ** -0.5)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    q_pos = pos0[:, None] + jnp.arange(c)[None, :]               # (B, C)
    k_pos = jnp.arange(k.shape[2])                               # (M*bs,)
    ok = (jnp.arange(c)[None, :] < n_live[:, None])[:, :, None] \
        & (k_pos[None, None, :] <= q_pos[:, :, None])            # (B, C, K)
    scores = jnp.where(ok[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1) * ok[:, None]
    out = jnp.einsum("bhck,bhkd->bhcd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def quantize_kv_blocks_ref(blocks):
    """Loop-form oracle for per-(block, head) int8 KV quantization.

    Quantizes each (block, head) slice independently with its own scale
    ``max|x| / 127``; head-major blocks carry the head axis at -3, and
    blocks without one (ndim < 4) get one scale per block.  Returns
    (q int8, scales float32 keepdims), matching ``ops.quantize_kv_blocks``.
    """
    import numpy as np
    v = np.asarray(blocks, dtype=np.float32)
    if v.ndim >= 4:
        axes = tuple(i for i in range(1, v.ndim) if i != v.ndim - 3)
    else:
        axes = tuple(range(1, v.ndim))
    amax = np.max(np.abs(v), axis=axes, keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = np.clip(np.round(v / scale), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(scale.astype(np.float32))


def dequantize_kv_blocks_ref(q, scale, dtype=jnp.bfloat16):
    """Oracle inverse of :func:`quantize_kv_blocks_ref`."""
    import numpy as np
    out = np.asarray(q, dtype=np.float32) * np.asarray(scale,
                                                       dtype=np.float32)
    return jnp.asarray(out).astype(dtype)


def rglru_scan_ref(a, b, h0):
    """Sequential linear recurrence. a, b: (B,S,R); h0: (B,R) fp32."""
    def step(h, ab):
        a_t, b_t = ab
        h = a_t.astype(jnp.float32) * h + b_t.astype(jnp.float32)
        return h, h

    a_t = jnp.moveaxis(a, 1, 0)
    b_t = jnp.moveaxis(b, 1, 0)
    h_last, hs = jax.lax.scan(step, h0.astype(jnp.float32), (a_t, b_t))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype), h_last


def rwkv6_scan_ref(r, k, v, w, u, s0):
    """Sequential WKV. r,k,v,w: (B,H,S,N); u: (H,N); s0: (B,H,N,N) fp32."""
    f32 = jnp.float32

    def step(s, xs):
        r_t, k_t, v_t, w_t = (x.astype(f32) for x in xs)   # (B,H,N)
        bonus = jnp.einsum("bhk,hk,bhk->bh", r_t, u.astype(f32), k_t)
        y = jnp.einsum("bhk,bhkn->bhn", r_t, s) + bonus[..., None] * v_t
        s = w_t[..., None] * s + k_t[..., None] * v_t[..., None, :]
        return s, y

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (r, k, v, w))
    s_last, ys = jax.lax.scan(step, s0.astype(f32), xs)
    return jnp.moveaxis(ys, 0, 2).astype(r.dtype), s_last
