"""Residual blocks: attention / RG-LRU / RWKV, each with norm + FFN."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import rglru, rwkv6
from repro.models.context import RunContext
from repro.models.layers import apply_norm, apply_rope, attention_xla, mlp
from repro.models.moe import moe_apply, moe_specs
from repro.models.spec import ParamSpec


# --------------------------------------------------------------------------- #
# Specs
# --------------------------------------------------------------------------- #
def norm_specs(cfg: ModelConfig):
    d = cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), init="zeros")}
    return {"scale": ParamSpec((d,), ("embed",), init="ones"),
            "bias": ParamSpec((d,), ("embed",), init="zeros")}


def mlp_specs(cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    sp = {"wi": ParamSpec((d, f), ("embed", "mlp")),
          "wo": ParamSpec((f, d), ("mlp", "embed"), fan_in=f)}
    if cfg.mlp_gated:
        sp["wg"] = ParamSpec((d, f), ("embed", "mlp"))
    return sp


def attn_specs(cfg: ModelConfig):
    d, hq, hkv, n = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sp = {
        "wq": ParamSpec((d, hq, n), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, hkv, n), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, hkv, n), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((hq, n, d), ("heads", "head_dim", "embed"),
                        fan_in=hq * n),
    }
    if cfg.qkv_bias:
        sp["bq"] = ParamSpec((hq, n), ("heads", "head_dim"), init="zeros")
        sp["bk"] = ParamSpec((hkv, n), ("kv_heads", "head_dim"), init="zeros")
        sp["bv"] = ParamSpec((hkv, n), ("kv_heads", "head_dim"), init="zeros")
    return sp


def block_specs(cfg: ModelConfig, kind: str):
    sp = {"norm1": norm_specs(cfg), "norm2": norm_specs(cfg)}
    if kind == "attn":
        sp["attn"] = attn_specs(cfg)
        sp["ffn"] = moe_specs(cfg) if cfg.is_moe else mlp_specs(cfg)
    elif kind == "rglru":
        sp["rec"] = rglru.rglru_specs(cfg)
        sp["ffn"] = mlp_specs(cfg)
    elif kind == "rwkv":
        sp["tm"] = rwkv6.rwkv_time_specs(cfg)
        sp["cm"] = rwkv6.rwkv_channel_specs(cfg)
    else:
        raise ValueError(kind)
    return sp


# --------------------------------------------------------------------------- #
# Attention apply
# --------------------------------------------------------------------------- #
def _ring_positions(pos: jax.Array, window: int) -> jax.Array:
    """Absolute position stored in each ring-buffer slot, given cursor pos."""
    idx = jnp.arange(window)
    return pos - ((pos - idx) % window)


def _gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Head-major block pool (N, Hkv, bs, D) gathered through per-row
    block tables (B, M) into contiguous (B, M*bs, Hkv, D) KV."""
    pages = jnp.swapaxes(pool[block_tables], 2, 3)       # (B, M, bs, Hkv, D)
    b, m, bs = pages.shape[:3]
    return pages.reshape((b, m * bs) + pages.shape[3:])


def attn_apply(params: dict, x: jax.Array, cfg: ModelConfig, ctx: RunContext,
               rope: Tuple[jax.Array, jax.Array], cache: Optional[dict],
               mode: str, prefix_len: int, pos,
               cache_capacity: int = 0, block_tables=None,
               block_size: int = 0) -> Tuple[jax.Array, Optional[dict]]:
    cos, sin = rope
    q = jnp.einsum("bsd,dhn->bshn", x, params["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bsd,dhn->bshn", x, params["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dhn->bshn", x, params["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    # --- attention sharding mode (DESIGN.md §4) ---
    # heads-TP when n_heads divides the model axis; otherwise sequence-
    # parallel Q (tiny GQA K/V replicated over model) so the score tensor
    # is always sharded over the model axis.
    seq_mode = False
    seq_shards = 1
    constrain_cb = None
    if ctx.mesh is not None and mode not in ("decode", "chunk"):
        m = ctx.model_axis
        msz = ctx.model_size
        from repro.models.model import constrain
        use_seq = (ctx.zero_sp or cfg.n_heads % msz != 0) \
            and x.shape[1] % msz == 0
        if use_seq:
            seq_mode = True
            seq_shards = msz
            q = constrain(q, ctx, m, None, None)
            k = constrain(k, ctx, None, None, None)
            v = constrain(v, ctx, None, None, None)

            def constrain_cb(t):
                # pin the sharded q-row block dim (dim 1) to the model axis
                return constrain(t, ctx, m, *([None] * (t.ndim - 2)))
        elif cfg.n_heads % msz == 0:
            q = constrain(q, ctx, None, m, None)
            kv_m = m if cfg.n_kv_heads % msz == 0 else None
            k = constrain(k, ctx, None, kv_m, None)
            v = constrain(v, ctx, None, kv_m, None)

    new_cache = None
    if mode == "decode" and block_tables is not None:
        # Paged decode: the cache leaves are a head-major physical block
        # pool (num_blocks, Hkv, block_size, D) shared by every slot (one
        # block of one head is a contiguous (bs, D) tile, the unit the
        # paged kernels stage into VMEM); each row of
        # the batch is one slot with its own cursor ``pos[i]`` and its own
        # row of ``block_tables``.  The new token's K/V lands in the slot's
        # current block; attention gathers the slot's blocks in logical
        # order.  Inactive slots point every table entry at block 0 (the
        # reserved trash block), so their writes can never corrupt live KV.
        if cfg.window is not None:
            raise NotImplementedError("paged KV cache requires full "
                                      "attention (cfg.window=None)")
        bsz = x.shape[0]
        rows = jnp.arange(bsz)
        blk = block_tables[rows, pos // block_size]          # (B,)
        off = pos % block_size
        ck = cache["k"].at[blk, :, off].set(k[:, 0])
        cv = cache["v"].at[blk, :, off].set(v[:, 0])
        if ctx.kernels:
            from repro.kernels import ops as kops
            out = kops.paged_attention(q, ck, cv, block_tables, pos + 1,
                                       softcap=cfg.logit_softcap,
                                       fused=ctx.paged_fused,
                                       interpret=ctx.interpret)
        else:
            kg = _gather_pages(ck, block_tables)
            vg = _gather_pages(cv, block_tables)
            out = attention_xla(q, kg, vg, causal=True, window=None,
                                softcap=cfg.logit_softcap, q_offset=pos)
        new_cache = {"k": ck, "v": cv}
    elif mode == "chunk":
        # Chunked paged prefill (ADR-005): each batch row carries a C-token
        # chunk of its uncached suffix.  ``pos`` is (pos0, n_live) — the
        # chunk's starting cursor and its live token count (0..C; 0 = dead
        # row).  The chunk's K/V is scattered into the slot's paged blocks
        # through the block table, then attention runs over all previously
        # resident blocks plus the chunk itself (causal).  Writes mirror the
        # stepwise scan exactly: dead tokens write block 0 (trash), tokens
        # clamped at capacity-1 collapse to one write holding the *last*
        # live token's K/V (last-live-wins = the stepwise final state).
        if cfg.window is not None:
            raise NotImplementedError("chunked prefill requires full "
                                      "attention (cfg.window=None)")
        pos0, n_live = pos
        bsz, csz = x.shape[0], x.shape[1]
        cap = cache_capacity
        cidx = jnp.arange(csz)
        cpos = pos0[:, None] + cidx[None, :]                 # (B, C)
        live = cidx[None, :] < n_live[:, None]
        wpos = jnp.minimum(cpos, cap - 1)
        writer = live & ((cpos < cap - 1)
                         | (cidx[None, :] == n_live[:, None] - 1))
        blk_col = jnp.minimum(wpos // block_size,
                              block_tables.shape[1] - 1)
        blk = jnp.where(writer,
                        jnp.take_along_axis(block_tables, blk_col, axis=1), 0)
        off = jnp.where(writer, wpos % block_size, 0)
        ck = cache["k"].at[blk, :, off].set(k)
        cv = cache["v"].at[blk, :, off].set(v)
        if ctx.kernels:
            from repro.kernels import ops as kops
            out = kops.paged_prefill(q, ck, cv, block_tables, pos0, n_live,
                                     softcap=cfg.logit_softcap,
                                     interpret=ctx.interpret)
        else:
            kg = _gather_pages(ck, block_tables)
            vg = _gather_pages(cv, block_tables)
            out = attention_xla(q, kg, vg, causal=True, window=None,
                                softcap=cfg.logit_softcap, q_offset=pos0)
        new_cache = {"k": ck, "v": cv}
    elif mode == "decode":
        capacity = cache["k"].shape[1]
        if cfg.window is not None and capacity == cfg.window:
            slot = pos % capacity
            k_pos = _ring_positions(pos, capacity)
        else:
            slot = jnp.minimum(pos, capacity - 1)
            k_pos = jnp.arange(capacity)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        k_pos = jnp.broadcast_to(k_pos, (x.shape[0], capacity))
        out = attention_xla(q, ck, cv, causal=True, window=cfg.window,
                            softcap=cfg.logit_softcap, q_offset=pos,
                            k_pos=k_pos)
        new_cache = {"k": ck, "v": cv}
    else:
        if ctx.kernels and cfg.causal and prefix_len == 0:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=True,
                                       window=cfg.window,
                                       softcap=cfg.logit_softcap,
                                       interpret=ctx.interpret)
        else:
            out = attention_xla(q, k, v, causal=cfg.causal, window=cfg.window,
                                prefix_len=prefix_len,
                                softcap=cfg.logit_softcap,
                                seq_shards=seq_shards,
                                constrain_cb=constrain_cb,
                                unroll_chunks=ctx.scan_unroll)
        if mode == "prefill":
            w = cfg.window
            s = x.shape[1]
            if w is not None and s >= w:
                # ring cache; prefill length is a multiple of the window in
                # all assigned shapes, so slots line up with positions mod w
                new_cache = {"k": k[:, -w:], "v": v[:, -w:]}
            else:
                cap = cache_capacity or s
                pad = [(0, 0), (0, cap - s), (0, 0), (0, 0)]
                new_cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
            if ctx.mesh is not None:
                # pin the produced cache to its storage sharding (kv_heads
                # or seq over model) so the stacked scan output is never
                # materialized replicated
                from repro.models.model import constrain
                m2 = ctx.model_axis
                kv_m = m2 if (cfg.n_kv_heads % ctx.model_size == 0
                              and not ctx.zero_sp) else None
                seq_m = None if kv_m else m2
                new_cache = {
                    kk: constrain(vv, ctx, seq_m, kv_m, None)
                    for kk, vv in new_cache.items()}
    if seq_mode and not ctx.zero_sp:
        from repro.models.model import constrain
        out = constrain(out, ctx, None, None, None)   # gather seq shards
    elif seq_mode:
        from repro.models.model import constrain
        out = constrain(out, ctx, ctx.model_axis, None, None)  # stay sharded
    out = jnp.einsum("bshn,hnd->bsd", out, params["wo"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    return out, new_cache


# --------------------------------------------------------------------------- #
# Generic residual block
# --------------------------------------------------------------------------- #
def block_apply(kind: str, params: dict, x: jax.Array, cfg: ModelConfig,
                ctx: RunContext, rope, cache: Optional[dict], mode: str,
                prefix_len: int, pos, cache_capacity: int = 0,
                block_tables=None, block_size: int = 0):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(x, params["norm1"], cfg.norm_type)
    if kind == "attn":
        mix, mix_cache = attn_apply(params["attn"], h, cfg, ctx, rope,
                                    cache, mode, prefix_len, pos,
                                    cache_capacity, block_tables, block_size)
    elif kind == "rglru":
        mix, mix_cache = rglru.rglru_block_apply(params["rec"], h, cfg, ctx,
                                                 cache, mode)
    elif kind == "rwkv":
        tm_cache = cache["tm"] if cache is not None else None
        mix, mix_cache = rwkv6.rwkv_time_apply(params["tm"], h, cfg, ctx,
                                               tm_cache, mode)
    else:
        raise ValueError(kind)
    x = x + mix

    h2 = apply_norm(x, params["norm2"], cfg.norm_type)
    ffn_cache = None
    if kind == "rwkv":
        cm_cache = cache["cm"] if cache is not None else None
        ffn, ffn_cache = rwkv6.rwkv_channel_apply(params["cm"], h2, cfg,
                                                  cm_cache, mode)
    elif kind == "attn" and cfg.is_moe:
        ffn, aux = moe_apply(params["ffn"], h2, cfg, ctx)
    else:
        ffn = mlp(params["ffn"], h2, cfg.mlp_act, cfg.mlp_gated)
    x = x + ffn

    if kind == "rwkv":
        new_cache = ({"tm": mix_cache, "cm": ffn_cache}
                     if mix_cache is not None else None)
    else:
        new_cache = mix_cache
    return x, new_cache, aux


def block_cache_axes(cfg: ModelConfig, kind: str):
    """Logical sharding axes mirroring ``init_block_cache`` structure.

    kv_heads takes the model axis when divisible; otherwise the cache
    sequence dim does ("kv_seq" is lower priority than "kv_heads" in
    distributed.sharding._PRIORITY, so exactly one of them claims it).
    """
    if kind == "attn":
        kv = ("batch", "kv_seq", "kv_heads", "head_dim")
        return {"k": kv, "v": kv}
    if kind == "rglru":
        return {"h": ("batch", "lru"), "conv": ("batch", None, "lru")}
    if kind == "rwkv":
        return {"tm": {"prev": ("batch", None),
                       "s": ("batch", "heads", None, None)},
                "cm": {"prev": ("batch", None)}}
    raise ValueError(kind)


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, capacity: int,
                     dtype):
    """Zero cache for one block."""
    if kind == "attn":
        cap = min(capacity, cfg.window) if cfg.window else capacity
        shape = (batch, cap, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == "rglru":
        return rglru.init_rglru_cache(cfg, batch, dtype)
    if kind == "rwkv":
        return rwkv6.init_rwkv_cache(cfg, batch, dtype)
    raise ValueError(kind)
