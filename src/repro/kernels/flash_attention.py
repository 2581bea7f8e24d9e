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
    """One (slot, query head, kv-block) step of the per-head paged grid.

    The A/B baseline of :func:`_paged_decode_chunk_kernel`: the grid
    (B, Hq, M) gives every query head its own (1, d) tile and re-stages
    each KV block once per query head.  The block table and context
    lengths arrive as scalar prefetch so the KV BlockSpec index map can
    chase ``tbl_ref``; one staged block is a contiguous (bs, d) tile of one
    KV head.  Online-softmax state ((1, 1)/(1, d) m, l, acc) persists in
    VMEM scratch across the sequential block grid dimension.
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


def _paged_decode_chunk_kernel(plan_ref, len_ref, q_ref, *refs, scale: float,
                               softcap: Optional[float], bs: int, p: int,
                               nchunk: int):
    """One (slot, chunk of P logical blocks) step of decode attention.

    Every KV head of the slot is computed in the step: head h's (g, d)
    query tile meets the chunk's (P*bs, d) keys and values.  ``refs`` holds
    P staged K blocks, P staged V blocks, the output and the softmax state;
    staged block i is one contiguous (Hkv, bs, d) tile of the head-major
    pool, the physical block ``plan_ref`` names for this step's input i
    (:func:`_paged_stage_plan`).  Each is its own input, so the pipeline
    copies the next step's blocks, across slot boundaries too, while this
    step computes, and copies nothing for an input whose block is the one
    it already holds.

    A chunk at or past a slot's context computes nothing; positions past
    it inside the last live chunk are masked.  Their staged values are
    pool data (finite) and get exactly zero weight.  A slot with no
    context outputs exact zeros.
    """
    k_refs, v_refs = refs[:p], refs[p:2 * p]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * p:]
    b = pl.program_id(0)
    c = pl.program_id(1)
    ctx = len_ref[b]
    span = p * bs                                 # tokens one step covers
    hkv, g = q_ref.shape[0], q_ref.shape[1]
    del plan_ref                                  # consumed by the index maps

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c * span < ctx)                      # chunk holds context
    def _compute():
        k_pos = c * span + jax.lax.broadcasted_iota(jnp.int32, (g, span), 1)
        for h in range(hkv):
            k = jnp.concatenate([r[h].astype(jnp.float32) for r in k_refs])
            v = jnp.concatenate([r[h].astype(jnp.float32) for r in v_refs])
            s = jax.lax.dot_general(q_ref[h].astype(jnp.float32), k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(k_pos < ctx, s, _NEG_INF)
            _online_softmax_step(s, v, m_ref.at[h], l_ref.at[h],
                                 acc_ref.at[h])

    @pl.when(c == nchunk - 1)
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


# tokens of one slot a step of the decode kernel covers, and the VMEM its
# double-buffered K and V staging may take
_CHUNK_TOKENS = 128
_STAGING_BYTES = 4 << 20


def paged_chunk_blocks(m: int, hkv: int, bs: int, d: int,
                       itemsize: int) -> int:
    """P, the logical blocks one step of the decode kernel stages per slot:
    the largest P <= M with P*bs <= 128 tokens whose double-buffered K and
    V staging, 4*P*Hkv*bs*d*itemsize bytes, fits 4 MiB of VMEM."""
    p = max(1, min(m, _CHUNK_TOKENS // bs))
    while p > 1 and 4 * p * hkv * bs * d * itemsize > _STAGING_BYTES:
        p -= 1
    return p


def _paged_stage_plan(block_tables, context_lens, *, p: int, bs: int):
    """(B * ceil(M/P) * P,) int32: the physical block input i of grid step
    (b, c) stages, at flat index (b * ceil(M/P) + c) * P + i.

    Where logical block c*P + i holds context, it is that block.  Anywhere
    else (past a slot's context, a slot without one, M % P != 0) input i
    keeps the block it staged last, or, before its first live block, that
    block, so the pipeline copies nothing there; an input no context
    reaches stages its table entry once.
    """
    b, m = block_tables.shape
    nchunk = -(-m // p)
    j = jnp.arange(nchunk * p, dtype=jnp.int32)
    blk = block_tables[:, jnp.minimum(j, m - 1)].reshape(b * nchunk, p)
    n_live = (context_lens + bs - 1) // bs        # blocks a context spans
    live = (j[None, :] < n_live[:, None]).reshape(b * nchunk, p)
    step = jnp.arange(b * nchunk, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    first = jnp.argmax(live, axis=0).astype(jnp.int32)[None, :]
    src = jnp.where(last >= 0, last, first)
    return jnp.take_along_axis(blk, src, axis=0).reshape(-1)


def paged_kv_fetches(context_lens, hq: int, hkv: int, m: int, bs: int,
                     d: int, itemsize: int, *, fused: bool = True) -> int:
    """(block, KV head) tiles staged HBM->VMEM per decode step, per pool
    tensor, for slots holding ``context_lens`` tokens.

    The fused kernel (grid (B, ceil(M/P))) stages each block a slot's
    context spans once, all Hkv heads of it as one (Hkv, bs, d) tile:
    ceil(ctx/bs) blocks a slot, none for a slot without context.  Each of
    its P block inputs that no context reaches stages one block in the
    pipeline's first step.  The per-head kernel walks (B, Hq, M) and
    stages every block of the table once per query head, live or not.  At
    full context (every ``ctx == M*bs``) per-head = g x fused.  The
    benchmark (benchmarks/decode_micro.py) reports this, so it must stay
    in lockstep with the grids below.
    """
    if not fused:
        return len(context_lens) * hq * m
    live = [min(max(-(-int(n) // bs), 0), m) for n in context_lens]
    p = paged_chunk_blocks(m, hkv, bs, d, itemsize)
    return hkv * (sum(live) + p - min(p, max(live, default=0)))


def _paged_attention_chunked(q, k_pool, v_pool, block_tables, context_lens,
                             *, softcap, interpret):
    """The fused decode grid (B, ceil(M/P)); see
    :func:`_paged_decode_chunk_kernel`."""
    b, hq, _, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    p = paged_chunk_blocks(m, hkv, bs, d, k_pool.dtype.itemsize)
    nchunk = -(-m // p)
    plan = _paged_stage_plan(block_tables, context_lens, p=p, bs=bs)
    qg = q[:, :, 0, :].reshape(b, hkv, g, d)

    def q_map(b_, c, *_):
        return (b_, 0, 0, 0)

    def kv_map(i):
        def index(b_, c, plan_ref, lens_ref):
            return (plan_ref[(b_ * nchunk + c) * p + i], 0, 0, 0)
        return pl.BlockSpec((None, hkv, bs, d), index)

    kern = functools.partial(_paged_decode_chunk_kernel, scale=d ** -0.5,
                             softcap=softcap, bs=bs, p=p, nchunk=nchunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nchunk),
        in_specs=[pl.BlockSpec((None, hkv, g, d), q_map)]
        + [kv_map(i) for i in range(p)] * 2,
        out_specs=pl.BlockSpec((None, hkv, g, d), q_map),
        scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(plan, context_lens, qg, *[k_pool] * p, *[v_pool] * p)
    return out.reshape(b, hq, 1, d)


def paged_attention_bhsd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                         block_tables: jax.Array, context_lens: jax.Array, *,
                         softcap: Optional[float] = None, fused: bool = True,
                         interpret: bool = False) -> jax.Array:
    """Decode-time paged attention over a block-table KV pool.

    q: (B, Hq, 1, D) — one query token per slot;
    k_pool, v_pool: (N, Hkv, bs, D) — the shared head-major block pool;
    block_tables: (B, M) int32 — per-slot physical block ids, logical order;
    context_lens: (B,) int32 — tokens valid per slot.  Returns (B, Hq, 1, D).

    ``fused=True`` (default) runs the grid (B, ceil(M/P)): one step owns
    one slot and a chunk of P logical blocks (:func:`paged_chunk_blocks`,
    from the shapes alone) for all KV heads at once, stages only the
    blocks that hold context, by DMA, double-buffered across steps, and
    skips chunks past a slot's context.  ``fused=False`` keeps the
    per-query-head grid (B, Hq, M) for A/B measurement (see
    benchmarks/decode_micro.py).
    """
    if fused:
        return _paged_attention_chunked(q, k_pool, v_pool, block_tables,
                                        context_lens, softcap=softcap,
                                        interpret=interpret)
    b, hq, _, d = q.shape
    _, hkv, bs, _ = k_pool.shape
    m = block_tables.shape[1]
    g = hq // hkv
    qg = q[:, :, 0, :].reshape(b, hq, 1, d)

    def q_map(b_, h, j, tbl, cl):
        return (b_, h, 0, 0)

    def kv_map(b_, h, j, tbl, cl):
        return (tbl[b_, j], h // g, 0, 0)

    kern = functools.partial(_paged_decode_kernel, scale=d ** -0.5,
                             softcap=softcap, bs=bs, nblk=m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, m),
        in_specs=[
            pl.BlockSpec((None, None, 1, d), q_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, 1, d), q_map),
        scratch_shapes=_paged_scratch(1, d),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
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
