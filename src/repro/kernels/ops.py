"""jit'd public wrappers for the Pallas kernels.

Handles layout (models use (B,S,H,D); kernels want (B,H,S,D)) and padding
to block multiples.  This module also makes the one decision of how
attention runs on each platform (:func:`serving_impl`,
:func:`_interpret`): on the TPU the kernels compile to Mosaic; on the CPU
the serving stack takes the XLA path and a kernel runs in Pallas
interpret mode only when its caller asks for it (``interpret=True``, the
kernel tests); any other platform is an error, never a silent
interpreter.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import compression as _comp
from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import rwkv6_scan as _rw


def serving_impl() -> str:
    """The attention implementation the serving stack runs here:
    ``"pallas"`` (Mosaic kernels) on the TPU, ``"xla"`` on the CPU."""
    platform = jax.default_backend()
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "xla"
    raise RuntimeError(f"no attention implementation for platform "
                       f"{platform!r}: kernels compile for the TPU, the "
                       f"XLA fallback is kept for the CPU")


def _interpret(interpret: bool) -> bool:
    """Run a kernel interpreted only when the caller asked; otherwise it
    must compile for the TPU."""
    if interpret:
        return True
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(f"Pallas kernels compile only for the TPU, not "
                           f"{platform!r}; pass interpret=True to run one "
                           f"in the Pallas interpreter")
    return False


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None, bq: int = 128,
                    bk: int = 128, interpret: bool = False):
    """Model-layout wrapper. q: (B,S,Hq,D); k,v: (B,T,Hkv,D) -> (B,S,Hq,D)."""
    interpret = _interpret(interpret)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq = min(bq, max(8, qt.shape[2]))
    bk = min(bk, max(8, kt.shape[2]))
    qt, s0 = _pad_to(qt, 2, bq)
    kt, t0 = _pad_to(kt, 2, bk)
    vt, _ = _pad_to(vt, 2, bk)
    out = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   softcap=softcap, bq=bq, bk=bk, q_len=s0,
                                   k_len=t0, interpret=interpret)
    return jnp.swapaxes(out[:, :, :s0], 1, 2)


@functools.partial(jax.jit, static_argnames=("softcap", "fused", "interpret"))
def paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                    softcap: Optional[float] = None, fused: bool = True,
                    interpret: bool = False):
    """Decode-time paged attention, model layout.

    q: (B, 1, Hq, D) — the current token's query per slot;
    k_pool, v_pool: (N, Hkv, bs, D) head-major physical KV block pool;
    block_tables: (B, M) int32; context_lens: (B,) int32.
    Returns (B, 1, Hq, D).  The kernel gathers KV blocks through the block
    table with scalar prefetch, so slots scattered anywhere in the pool cost
    the same as a contiguous cache.

    ``fused=True`` (default) is the chunked flash-decoding grid: one step
    per slot and chunk of P blocks, each live KV block staged once for all
    KV heads, the g = Hq/Hkv query heads of a KV head one (g, d) tile.
    ``fused=False`` keeps the per-query-head grid for A/B measurement
    (benchmarks/decode_micro.py).
    """
    interpret = _interpret(interpret)
    qt = jnp.swapaxes(q, 1, 2)                   # (B, Hq, 1, D)
    out = _fa.paged_attention_bhsd(
        qt, k_pool, v_pool, block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32), softcap=softcap, fused=fused,
        interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_prefill(q, k_pool, v_pool, block_tables, pos0, n_live, *,
                  softcap: Optional[float] = None,
                  interpret: bool = False):
    """Chunked-prefill paged attention, model layout.

    q: (B, C, Hq, D) — one C-token suffix chunk per slot (the chunk's KV
    must already be scattered into the pool);
    k_pool, v_pool: (N, Hkv, bs, D) head-major physical KV block pool;
    block_tables: (B, M) int32; pos0, n_live: (B,) int32 (chunk start
    position / live token count per slot).  Returns (B, C, Hq, D) with
    rows at chunk positions >= n_live exactly zero.

    The chunked generalization of :func:`paged_attention`: one dispatch
    covers C suffix tokens per slot instead of one, attending over all
    previously resident blocks plus the chunk itself (causal), via the
    same GQA-fused scalar-prefetch block-table gather.
    """
    interpret = _interpret(interpret)
    qt = jnp.swapaxes(q, 1, 2)                   # (B, Hq, C, D)
    out = _fa.paged_prefill_bhsd(
        qt, k_pool, v_pool, block_tables.astype(jnp.int32),
        pos0.astype(jnp.int32), n_live.astype(jnp.int32),
        softcap=softcap, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_verify(q, k_pool, v_pool, block_tables, pos0, n_live, *,
                 softcap: Optional[float] = None,
                 interpret: bool = False):
    """Speculative-verification paged attention, model layout.

    q: (B, C, Hq, D) — per slot, the queries of the current token plus its
    ``k_i`` draft proposals (C = K+1 padded; the window's KV must already
    be scattered into the pool); block_tables: (B, M) int32; pos0 (B,)
    the slot cursor; n_live (B,) = ``k_i + 1`` live window tokens (0 =
    dead row).  Returns (B, C, Hq, D) with rows >= n_live exactly zero.

    This is the per-row *variable-K* generalization the verification path
    needs (docs/architecture.md ADR-008), and it is exactly the
    ``paged_prefill`` contract: the GQA-fused chunk kernel already masks
    per row with ``q_chunk < n_live[b]`` and causally with
    ``k_pos <= pos0[b] + q_chunk``, so every slot scores all K+1
    positions in ONE kernel call per layer — one (C*g, d) MXU tile per
    (slot, group, kv-block) — regardless of how many proposals each slot
    brought.  Stale KV from previously rejected tokens sits at positions
    beyond ``pos0 + n_live - 1`` and is causally masked off; positions
    below that were overwritten by this window's scatter before the call
    (write-then-attend), which is the whole containment argument for
    lossless speculation.  Kept as a named entry point so the verify
    path's kernel contract is explicit and can diverge (e.g. a fused
    accept reduction) without touching the prefill path.
    """
    return paged_prefill(q, k_pool, v_pool, block_tables, pos0, n_live,
                         softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("axis",))
def copy_blocks(leaf, src, dst, *, axis: int = 0):
    """Device-side KV block copy: ``leaf[dst] = leaf[src]`` along ``axis``.

    The copy-on-write primitive of the prefix cache (docs/architecture.md
    ADR-003): when a new prompt diverges partway into a cached block, the
    allocator maps a *fresh* block for the slot and the serving layer copies
    the cached block's contents into it on device — the slot then overwrites
    the divergent tail in place while the shared source stays immutable.

    src, dst: (C,) int32 physical block ids.  Pairs are independent (every
    dst is freshly allocated, so no pair's dst is another pair's src);
    (0, 0) pairs are harmless no-ops, which is what lets callers pad the
    pair list to a fixed bucket size.  Runs as one fused gather+scatter —
    one dispatch per pool leaf regardless of the number of pairs.
    """
    with jax.named_scope("copy_blocks"):
        moved = jnp.moveaxis(leaf, axis, 0)
        moved = moved.at[dst].set(moved[src])
        return jnp.moveaxis(moved, 0, axis)


@jax.jit
def quantize_kv_blocks(blocks):
    """Device-side per-(block, head) int8 quantization of KV blocks.

    ``blocks``: (n, Hkv, bs, D) — KV blocks gathered along the pool's
    block axis (leaves without a head axis fall back to per-block
    scales).  Returns ``(q int8, scales float32 keepdims)``.  The wire
    half of compressed KV transfer (docs/architecture.md ADR-009): a
    disaggregated prefill→decode handoff ships the int8 payload plus the
    scales over the inter-clone link instead of the full-width blocks,
    ~4x fewer modeled bytes at bf16 pools.
    """
    return _comp.quantize_kv_blocks(blocks)


@functools.partial(jax.jit, static_argnames=("dtype",))
def dequantize_kv_blocks(q, scales, *, dtype=jnp.bfloat16):
    """Device-side inverse of :func:`quantize_kv_blocks`.

    Runs on the receiving clone before the blocks are scattered into its
    pool; tokens decoded from dequantized KV may drift from the
    uncompressed path within the declared int8 tolerance.
    """
    return _comp.dequantize_kv_blocks(q, scales, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("bs", "br", "interpret"))
def rglru_scan(a, b, h0=None, *, bs: int = 256, br: int = 128,
               interpret: bool = False):
    """a, b: (B,S,R) recurrence coefficients; h0: (B,R) or None.

    Returns (h_seq (B,S,R), h_last (B,R) fp32).
    """
    interpret = _interpret(interpret)
    bsz, s, r = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, r), jnp.float32)
    bs = min(bs, s)
    br = br if r % br == 0 else r
    a_p, s0 = _pad_to(a, 1, bs)
    b_p, _ = _pad_to(b, 1, bs)
    pad = a_p.shape[1] - s0
    if pad:
        # padded steps: a=1, b=0 -> state carries through unchanged
        a_p = a_p.at[:, s0:].set(1.0)
    y, hn = _rg.rglru_scan_bsr(a_p.astype(jnp.float32),
                               b_p.astype(jnp.float32),
                               h0.astype(jnp.float32), bs=bs, br=br,
                               out_dtype=a.dtype, interpret=interpret)
    return y[:, :s0], hn


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0=None, *, chunk: int = 128,
               interpret: bool = False):
    """Model-layout wrapper. r,k,v,w: (B,S,H,N); u: (H,N); s0: (B,H,N,N).

    Returns (y (B,S,H,N), s_final (B,H,N,N) fp32).
    """
    interpret = _interpret(interpret)
    b, s, h, n = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)
    chunk = min(chunk, s)
    rt, kt, vt, wt = (jnp.swapaxes(x, 1, 2) for x in (r, k, v, w))
    rt, len0 = _pad_to(rt, 2, chunk)
    kt, _ = _pad_to(kt, 2, chunk)
    vt, _ = _pad_to(vt, 2, chunk)
    # padded steps: w=1 (state unchanged), k=0 (no injection)
    pad = rt.shape[2] - len0
    if pad:
        wt = jnp.concatenate(
            [wt, jnp.ones((b, h, pad, n), wt.dtype)], axis=2)
    y, sn = _rw.rwkv6_scan_bhsn(rt, kt, vt, wt, u, s0, chunk=chunk,
                                interpret=interpret)
    return jnp.swapaxes(y[:, :, :len0], 1, 2), sn
