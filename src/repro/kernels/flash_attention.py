"""Flash attention Pallas TPU kernel (causal / sliding-window / GQA / softcap).

TPU adaptation (DESIGN.md §6): the GPU flash-attention algorithm is re-tiled
for the TPU memory hierarchy — (bq x d) query tiles and (bk x d) KV tiles are
staged HBM->VMEM by BlockSpecs; the (bq x bk) score tile hits the MXU; the
online-softmax running state (m, l, acc) lives in VMEM scratch that persists
across the sequential kv grid dimension.  GQA is expressed in the KV index
map (q-head h reads kv-head h // group), so no KV duplication ever reaches
VMEM.  Fully-masked kv tiles are skipped with ``pl.when``.

Layout: q (B, Hq, S, D);  k, v (B, Hkv, T, D);  out (B, Hq, S, D).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], nk: int, bq: int, bk: int,
            q_len: int, k_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)
    k_pos = ki * bk + jax.lax.iota(jnp.int32, bk)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # static skip is impossible on a sequential TPU grid; predicate instead
    block_live = ki >= 0
    if causal:
        block_live &= (ki * bk) <= (qi * bq + bq - 1)
    if window is not None:
        block_live &= (ki * bk + bk) > (qi * bq - window)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        ok = (k_pos[None, :] < k_len) & (q_pos[:, None] < q_len)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(ok, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _online_softmax_step(s, v, m_ref, l_ref, acc_ref):
    """Fold one (rows, bs) score tile and its (bs, d) values into the
    running softmax state: m, l are (rows, 1) and acc is (rows, d).

    Every value is 2-D: Mosaic lays vectors out in (sublane, lane) tiles
    and refuses to reshape a 1-D row vector (or mask) into a column, so
    reductions keep their dims instead."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_scores(q_ref, k_ref, *, scale: float, softcap: Optional[float]):
    """(rows, d) query tile x (bs, d) key block -> (rows, bs) scores."""
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    return s


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                         l_ref, acc_ref, *, scale: float,
                         softcap: Optional[float], bs: int, nblk: int):
    """One (slot, head-group, kv-block) step of decode-time paged attention.

    One body serves both grids (the per-head grid is exactly the g=1 shape
    of the fused one).  Fused flash-decoding grid (B, Hkv, M): all
    ``g = Hq/Hkv`` query heads of a GQA group are computed as one (g, d)
    tile against each KV block — every block staged HBM->VMEM exactly once
    per group (g x less KV traffic) and the score matmul is a real
    (g, d) x (d, bs) MXU tile rather than g separate matvecs.  Per-head
    A/B grid (B, Hq, M): the same body with g=1 query tiles, re-staging
    each block once per query head.

    The block table and context lengths arrive as scalar prefetch so the
    KV BlockSpec index map can chase ``tbl_ref`` — only the blocks a slot
    actually owns are ever staged into VMEM; there is no materialized
    (B, M*bs, ...) gather.  The pool is head-major, so one staged block is
    a contiguous (bs, d) tile of one KV head.  Online-softmax state
    ((g, 1)/(g, d) m, l, acc) persists in VMEM scratch across the
    sequential block grid dimension.
    """
    del tbl_ref                                   # consumed by the index maps
    b = pl.program_id(0)
    j = pl.program_id(2)
    ctx = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs < ctx)                        # block holds written slots
    def _compute():
        s = _paged_scores(q_ref, k_ref, scale=scale, softcap=softcap)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < ctx, s, _NEG_INF)
        _online_softmax_step(s, v_ref[...].astype(jnp.float32), m_ref,
                             l_ref, acc_ref)

    @pl.when(j == nblk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_chunk_kernel(tbl_ref, p0_ref, nl_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, scale: float,
                        softcap: Optional[float], bs: int, g: int,
                        nblk: int):
    """One (slot, head-group, kv-block) step of chunked-prefill attention.

    The chunked-prefill sibling of :func:`_paged_decode_kernel`: instead of
    one query token per slot, each grid step owns a whole C-token chunk of
    the slot's uncached suffix — all ``g = Hq/Hkv`` query heads of the GQA
    group for all C chunk positions flattened into one (C*g, d) query tile,
    so each KV block is still staged HBM->VMEM exactly once per group and
    the score matmul is a real (C*g, d) x (d, bs) MXU tile.

    Query row ``r`` is chunk position ``r // g`` at absolute position
    ``p0 + r // g``; causal masking admits key position ``k_pos`` when
    ``k_pos <= p0 + r // g``, i.e. ``r >= (k_pos - p0) * g``, which covers
    both the previously resident prefix blocks and intra-chunk causality in
    one predicate (written without a vector division).  Rows past the
    chunk's live length (``r >= nl * g``) are forced to zero in the
    finalize step, matching the ref oracle's post-softmax re-mask.  Blocks
    entirely beyond the chunk's reach are predicated off with ``pl.when``;
    they are exact no-ops for live rows because block j=0 always computes
    and column 0 is always unmasked (m is finite before any skipped block).
    """
    del tbl_ref                                   # consumed by the index maps
    b = pl.program_id(0)
    j = pl.program_id(2)
    p0 = p0_ref[b]
    nl = nl_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((nl > 0) & (j * bs < p0 + nl))       # block reachable by chunk
    def _compute():
        s = _paged_scores(q_ref, k_ref, scale=scale, softcap=softcap)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (row < nl * g) & (row >= (k_pos - p0) * g)
        s = jnp.where(ok, s, _NEG_INF)
        _online_softmax_step(s, v_ref[...].astype(jnp.float32), m_ref,
                             l_ref, acc_ref)

    @pl.when(j == nblk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        row = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        o_ref[...] = jnp.where(row < nl * g, acc_ref[...] / l,
                               0.0).astype(o_ref.dtype)


def _paged_scratch(rows: int, d: int):
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32)]


def paged_prefill_bhsd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_tables: jax.Array, pos0: jax.Array,
                       n_live: jax.Array, *,
                       softcap: Optional[float] = None,
                       interpret: bool = False) -> jax.Array:
    """Chunked-prefill paged attention over a block-table KV pool.

    q: (B, Hq, C, D) — one C-token suffix chunk per slot (the chunk's KV
    must already be scattered into the pool);
    k_pool, v_pool: (N, Hkv, bs, D) — the shared head-major block pool;
    block_tables: (B, M) int32 — per-slot physical block ids, logical order;
    pos0: (B,) int32 — absolute position of each chunk's first token;
    n_live: (B,) int32 — live tokens in the chunk (0..C; 0 = dead row).
    Returns (B, Hq, C, D); rows at chunk positions >= n_live are exact 0.

    Always the GQA-fused grid (B, Hkv, M): q regrouped so one grid step owns
    a whole group's (C*g, d) tile — the chunked generalization of the C=1
    flash-decoding grid, sharing its scalar-prefetch block-table gather.

    ``n_live`` is per-row, NOT per-grid: two slots in the same dispatch may
    score different live lengths (slot a: 8 suffix tokens; slot b: 3).
    Speculative verification (``ops.paged_verify``, ADR-008) leans on
    exactly this — each slot's window is its current token plus a
    *variable* number of draft proposals ``k_i``, so ``n_live = k_i + 1``
    varies per row while the kernel call, grid, and tile shapes stay
    fixed at the padded C.  Dead query rows cost only masked lanes of the
    same MXU tile, never an extra kernel call or KV fetch.
    """
    b, hq, c, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    # (B, Hq, C, D) -> (B, Hkv, C*g, D): row r = chunk pos r // g, head r % g
    qg = q.reshape(b, hkv, g, c, d).swapaxes(2, 3).reshape(b, hkv, c * g, d)

    def q_map(b_, h, j, tbl, p0, nl):
        return (b_, h, 0, 0)

    def kv_map(b_, h, j, tbl, p0, nl):
        return (tbl[b_, j], h, 0, 0)

    kern = functools.partial(_paged_chunk_kernel, scale=d ** -0.5,
                             softcap=softcap, bs=bs, g=g, nblk=m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, m),
        in_specs=[
            pl.BlockSpec((None, None, c * g, d), q_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, c * g, d), q_map),
        scratch_shapes=_paged_scratch(c * g, d),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, d), q.dtype),
        interpret=interpret,
        name="paged_prefill",
    )(block_tables, pos0, n_live, qg, k_pool, v_pool)
    return out.reshape(b, hkv, c, g, d).swapaxes(2, 3).reshape(b, hq, c, d)


def paged_kv_fetches(b: int, hq: int, hkv: int, m: int, *,
                     fused: bool = True) -> int:
    """KV blocks staged HBM->VMEM per decode step, per pool tensor.

    Exactly the paged grid volume: the fused kernel walks (B, Hkv, M) and
    fetches each (slot, block) once per *group*; the per-head kernel walks
    (B, Hq, M) and re-stages every block g = Hq/Hkv times.  The benchmark
    (benchmarks/decode_micro.py) reports this, so it must stay in lockstep
    with the grids below.
    """
    return b * (hkv if fused else hq) * m


def paged_attention_bhsd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                         block_tables: jax.Array, context_lens: jax.Array, *,
                         softcap: Optional[float] = None, fused: bool = True,
                         interpret: bool = False) -> jax.Array:
    """Decode-time paged attention over a block-table KV pool.

    q: (B, Hq, 1, D) — one query token per slot;
    k_pool, v_pool: (N, Hkv, bs, D) — the shared head-major block pool;
    block_tables: (B, M) int32 — per-slot physical block ids, logical order;
    context_lens: (B,) int32 — tokens valid per slot.  Returns (B, Hq, 1, D).

    ``fused=True`` (default) runs the flash-decoding grid (B, Hkv, M): all
    g = Hq/Hkv query heads of a GQA group computed per KV block fetch.
    ``fused=False`` keeps the original per-query-head grid (B, Hq, M) for
    A/B measurement (see benchmarks/decode_micro.py).
    """
    b, hq, _, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    # one kernel body, two grids: fused walks KV heads with (g, d) query
    # tiles (q regrouped (B, Hq, 1, D) -> (B, Hkv, g, D) so one grid step
    # owns a whole GQA group); per-head walks query heads with g=1 tiles
    gq = g if fused else 1                        # query rows per grid step
    hg = hkv if fused else hq                     # head grid dimension
    qg = q[:, :, 0, :].reshape(b, hg, gq, d)
    kv_group = 1 if fused else g                  # query heads per KV head

    def q_map(b_, h, j, tbl, cl):
        return (b_, h, 0, 0)

    def kv_map(b_, h, j, tbl, cl):
        return (tbl[b_, j], h // kv_group, 0, 0)

    kern = functools.partial(_paged_decode_kernel, scale=d ** -0.5,
                             softcap=softcap, bs=bs, nblk=m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hg, m),
        in_specs=[
            pl.BlockSpec((None, None, gq, d), q_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, gq, d), q_map),
        scratch_shapes=_paged_scratch(gq, d),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hg, gq, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables, context_lens, qg, k_pool, v_pool)
    return out.reshape(b, hq, 1, d)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None, bq: int = 128,
                         bk: int = 128, q_len: Optional[int] = None,
                         k_len: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D). S, T must divide bq, bk."""
    b, hq, s, d = q.shape
    _, hkv, t, _ = k.shape
    g = hq // hkv
    nq, nk = s // bq, t // bk
    q_len = s if q_len is None else q_len
    k_len = t if k_len is None else k_len
    kern = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal, window=window,
        softcap=softcap, nk=nk, bq=bq, bk=bk, q_len=q_len, k_len=k_len)
    return pl.pallas_call(
        kern,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, qi, ki: (b_, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
