"""Per-kernel allclose vs pure-jnp oracles: shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,causal,window,softcap",
    [
        (1, 64, 2, 2, 32, True, None, None),
        (2, 128, 4, 1, 64, True, None, None),       # GQA 4:1
        (2, 128, 6, 2, 32, True, 32, None),         # sliding window
        (1, 96, 3, 3, 80, True, None, 30.0),        # softcap, unaligned d
        (1, 128, 2, 2, 16, False, None, None),      # encoder (non-causal)
        (2, 72, 5, 5, 24, True, None, None),        # unaligned seq (padding)
    ])
def test_flash_attention_matches_ref(b, s, hq, hkv, d, causal, window,
                                     softcap, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (b, s, hq, d), dtype)
    k = _rand(k2, (b, s, hkv, d), dtype)
    v = _rand(k3, (b, s, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, bq=32, bk=32, interpret=True)
    want = ref.flash_attention_ref(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, window=window, softcap=softcap).swapaxes(1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_block_size_invariance():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (1, 128, 2, 32), jnp.float32)
    k = _rand(k2, (1, 128, 2, 32), jnp.float32)
    v = _rand(k3, (1, 128, 2, 32), jnp.float32)
    outs = [np.asarray(ops.flash_attention(q, k, v, bq=bq, bk=bk,
                                           interpret=True))
            for bq, bk in [(32, 32), (64, 32), (128, 64), (128, 128)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# paged attention (decode-time block-table gather)
# --------------------------------------------------------------------------- #
def _paged_case(key, b, hq, hkv, d, bs, lens, dtype=jnp.float32, seed=0):
    """Random pool + a block table giving each slot distinct blocks."""
    max_blk = max(-(-ln // bs) for ln in lens)
    n_blocks = sum(-(-ln // bs) for ln in lens) + 1      # block 0 = trash
    k1, k2, k3 = jax.random.split(key, 3)
    q = _rand(k1, (b, hq, d), dtype)
    kp = _rand(k2, (n_blocks, hkv, bs, d), dtype)
    vp = _rand(k3, (n_blocks, hkv, bs, d), dtype)
    tables = np.zeros((b, max_blk), np.int32)
    nxt = 1
    for i, ln in enumerate(lens):
        for j in range(-(-ln // bs)):
            tables[i, j] = nxt
            nxt += 1
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(np.asarray(lens,
                                                                  np.int32))


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,d,bs,lens,softcap", [
    (2, 4, 2, 32, 8, (5, 16), None),            # GQA 2:1, ragged lengths
    (3, 6, 2, 32, 8, (1, 17, 32), None),        # boundary + full block
    (1, 3, 3, 16, 4, (11,), 20.0),              # softcap, MHA (group 1)
    (2, 8, 2, 16, 4, (3, 9), None),             # GQA 4:1
    # M = 20 blocks over P = 16: a partial second chunk; 0-context slots
    # between a full one (ctx = M*bs) and one ending mid-block, mid-chunk
    (4, 15, 5, 64, 8, (0, 160, 0, 77), None),   # smollm's GQA 3:1, d 64
    (3, 8, 8, 96, 8, (130, 0, 200), None),      # MHA at d 96, M = 25
    (2, 16, 2, 128, 8, (150, 45), 30.0),        # GQA 8:1, d 128, softcap
])
def test_paged_attention_kernel_matches_ref(b, hq, hkv, d, bs, lens, softcap,
                                            dtype, fused):
    q, kp, vp, tables, cls = _paged_case(KEY, b, hq, hkv, d, bs, lens, dtype)
    got = ops.paged_attention(q[:, None], kp, vp, tables, cls,
                              softcap=softcap, fused=fused, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, tables, cls, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got[:, 0], np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2), (8, 2)])   # groups 1/2/4
def test_paged_attention_fused_matches_per_head_kernel(hq, hkv):
    """The GQA-fused flash-decoding grid computes exactly what the per-head
    grid computes — fusion only changes KV staging, never the math — and it
    stages each block g x fewer times (the fetch accounting the benchmark
    reports)."""
    from repro.kernels.flash_attention import paged_kv_fetches
    lens = (5, 13, 24)
    q, kp, vp, tables, cls = _paged_case(KEY, 3, hq, hkv, 16, 8, lens)
    fused = ops.paged_attention(q[:, None], kp, vp, tables, cls,
                                fused=True, interpret=True)
    unfused = ops.paged_attention(q[:, None], kp, vp, tables, cls,
                                  fused=False, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=2e-6, rtol=2e-6)
    g = hq // hkv
    m = tables.shape[1]
    full = [m * 8] * 3
    assert paged_kv_fetches(full, hq, hkv, m, 8, 16, 4, fused=False) == \
        g * paged_kv_fetches(full, hq, hkv, m, 8, 16, 4, fused=True)


def test_paged_kv_fetches_counts_live_blocks():
    """The fused kernel stages each block a slot's context spans once, for
    all KV heads, and nothing for a slot without context; the per-head
    grid stages every table entry once per query head.  The count is the
    copies the kernel's stage plan asks of the pipeline: one per input
    wherever its block differs from the step before, and each input's
    first."""
    from repro.kernels.flash_attention import (_paged_stage_plan,
                                               paged_chunk_blocks,
                                               paged_kv_fetches)
    m, bs, hkv, hq, d = 20, 8, 5, 15, 64          # P = 16: chunks of 16 + 4
    p = paged_chunk_blocks(m, hkv, bs, d, 2)
    assert p == 16
    lens = [0, 160, 0, 77, 1, 129]                # 0, 20, 0, 10, 1, 17 blocks
    assert paged_kv_fetches(lens, hq, hkv, m, bs, d, 2) == hkv * 48
    assert paged_kv_fetches(lens, hq, hkv, m, bs, d, 2,
                            fused=False) == 6 * hq * m
    # inputs no context reaches stage one block each in the first step
    assert paged_kv_fetches([0, 3, 0], hq, hkv, m, bs, d, 2) == hkv * 16
    assert paged_kv_fetches([0, 0], hq, hkv, m, bs, d, 2) == hkv * 16
    tables = jnp.asarray(np.arange(1, 1 + 6 * m, dtype=np.int32)
                         .reshape(6, m))
    for ln in (lens, [0, 3, 0, 0, 0, 0], [m * bs] * 6):
        plan = np.asarray(_paged_stage_plan(
            tables, jnp.asarray(ln, jnp.int32), p=p, bs=bs)).reshape(-1, p)
        copies = p + int((plan[1:] != plan[:-1]).sum())
        assert hkv * copies == paged_kv_fetches(ln, hq, hkv, m, bs, d, 2)


def test_paged_attention_matches_contiguous_cache():
    """Gathering a slot's blocks through the table == attending over the
    same KV stored contiguously (the paged/contiguous equivalence that the
    serving layer relies on for token-identical mid-flight joins)."""
    lens = (5, 12, 8)
    q, kp, vp, tables, cls = _paged_case(KEY, 3, 4, 2, 32, 4, lens)
    got = ref.paged_attention_ref(q, kp, vp, tables, cls)
    for i, ln in enumerate(lens):
        nb = -(-ln // 4)
        # head-major blocks (nb, Hkv, bs, D) -> contiguous (nb*bs, Hkv, D)
        kc = np.asarray(kp)[np.asarray(tables)[i, :nb]].swapaxes(1, 2)
        vc = np.asarray(vp)[np.asarray(tables)[i, :nb]].swapaxes(1, 2)
        kc, vc = kc.reshape(-1, 2, 32), vc.reshape(-1, 2, 32)
        want = ref.flash_attention_ref(
            q[i:i + 1, :, None],
            jnp.swapaxes(jnp.asarray(kc[None, :ln]), 1, 2),
            jnp.swapaxes(jnp.asarray(vc[None, :ln]), 1, 2),
            causal=False)[:, :, 0]
        np.testing.assert_allclose(np.asarray(got[i:i + 1]),
                                   np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("fused", [True, False])
def test_paged_attention_ignores_stale_pool_contents(fused):
    """Positions past a slot's context length — the unwritten tail *inside*
    an allocated block, and the whole trash block — must never leak into
    its output, whatever garbage they hold."""
    q, kp, vp, tables, cls = _paged_case(KEY, 2, 2, 2, 16, 4, (3, 7))
    out0 = ops.paged_attention(q[:, None], kp, vp, tables, cls,
                               fused=fused, interpret=True)
    poisoned_k = kp.at[0].set(1e9)               # trash block
    poisoned_v = vp.at[0].set(-1e9)
    # unwritten tail inside allocated blocks: slot 0 (ctx 3) owns block 1,
    # its position 3 is unwritten; slot 1 (ctx 7) owns blocks 2,3 — block
    # 3's position 7 (offset 3) is unwritten
    blk0 = int(np.asarray(tables)[0, 0])
    blk1 = int(np.asarray(tables)[1, 1])
    poisoned_k = poisoned_k.at[blk0, :, 3].set(1e9).at[blk1, :, 3].set(1e9)
    poisoned_v = poisoned_v.at[blk0, :, 3].set(-1e9).at[blk1, :, 3].set(-1e9)
    out1 = ops.paged_attention(q[:, None], poisoned_k, poisoned_v, tables,
                               cls, fused=fused, interpret=True)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1))
    # same invariant for the reference oracle
    ref0 = ref.paged_attention_ref(q, kp, vp, tables, cls)
    ref1 = ref.paged_attention_ref(q, poisoned_k, poisoned_v, tables, cls)
    np.testing.assert_allclose(np.asarray(ref0), np.asarray(ref1))


@pytest.mark.parametrize("fused", [True, False])
def test_paged_attention_zero_context_slot_outputs_zero(fused):
    """A context_lens==0 row (empty/inactive slot) must output exact zeros
    in both kernel grids AND the oracle — not a softmax over garbage."""
    q, kp, vp, tables, cls = _paged_case(KEY, 2, 4, 2, 16, 4, (7, 8))
    cls = cls.at[1].set(0)
    want = np.asarray(ref.paged_attention_ref(q, kp, vp, tables, cls))
    np.testing.assert_array_equal(want[1], 0.0)
    got = np.asarray(ops.paged_attention(q[:, None], kp, vp, tables, cls,
                                         fused=fused, interpret=True))
    np.testing.assert_array_equal(got[1], 0.0)
    np.testing.assert_allclose(got[:, 0], want, atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# paged prefill (chunked suffix attention through the block table, ADR-005)
# --------------------------------------------------------------------------- #
def _prefill_case(key, b, hq, hkv, d, bs, c, pos0, dtype=jnp.float32):
    """Random pool + tables covering each slot's pos0 + c positions."""
    spans = [-(-(p + c) // bs) for p in pos0]
    max_blk = max(spans)
    n_blocks = sum(spans) + 1                    # block 0 = trash
    k1, k2, k3 = jax.random.split(key, 3)
    q = _rand(k1, (b, c, hq, d), dtype)
    kp = _rand(k2, (n_blocks, hkv, bs, d), dtype)
    vp = _rand(k3, (n_blocks, hkv, bs, d), dtype)
    tables = np.zeros((b, max_blk), np.int32)
    nxt = 1
    for i, nb in enumerate(spans):
        for j in range(nb):
            tables[i, j] = nxt
            nxt += 1
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(np.asarray(pos0,
                                                                  np.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,d,bs,c,pos0,n_live,softcap", [
    (2, 4, 2, 32, 8, 8, (0, 11), (8, 5), None),    # GQA 2:1, ragged chunks
    (3, 6, 2, 16, 8, 4, (5, 0, 8), (4, 0, 1), None),  # dead slot + boundary
    (1, 3, 3, 16, 4, 8, (3,), (6,), 20.0),         # softcap, MHA (group 1)
    (2, 8, 2, 16, 4, 1, (7, 2), (1, 1), None),     # C=1 degenerates to decode
])
def test_paged_prefill_kernel_matches_ref(b, hq, hkv, d, bs, c, pos0, n_live,
                                          softcap, dtype):
    q, kp, vp, tables, p0 = _prefill_case(KEY, b, hq, hkv, d, bs, c, pos0,
                                          dtype)
    nl = jnp.asarray(np.asarray(n_live, np.int32))
    got = ops.paged_prefill(q, kp, vp, tables, p0, nl, softcap=softcap,
                            interpret=True)
    want = ref.paged_prefill_ref(q.swapaxes(1, 2), kp, vp, tables, p0, nl,
                                 softcap=softcap).swapaxes(1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    # rows at chunk positions >= n_live (bucket padding) are exact zeros
    got_np = np.asarray(got, np.float32)
    for i, n in enumerate(n_live):
        np.testing.assert_array_equal(got_np[i, n:], 0.0)


def test_paged_prefill_matches_per_token_decode():
    """Chunk row t must equal the decode kernel's output for the same query
    at context length pos0+t+1 on the same pool — the equivalence that makes
    chunked prefill token-identical to the stepwise scan."""
    b, hq, hkv, d, bs, c = 2, 4, 2, 16, 4, 6
    pos0, n_live = (3, 8), (6, 4)
    q, kp, vp, tables, p0 = _prefill_case(KEY, b, hq, hkv, d, bs, c, pos0)
    nl = jnp.asarray(np.asarray(n_live, np.int32))
    chunk_out = np.asarray(ops.paged_prefill(q, kp, vp, tables, p0, nl,
                                             interpret=True))
    for t in range(c):
        lens = jnp.asarray([(p + t + 1) if t < n else 0
                            for p, n in zip(pos0, n_live)], jnp.int32)
        tok = ops.paged_attention(q[:, t:t + 1], kp, vp, tables, lens,
                                  interpret=True)
        np.testing.assert_allclose(chunk_out[:, t], np.asarray(tok[:, 0]),
                                   atol=2e-5, rtol=2e-5)


def test_paged_prefill_ignores_stale_pool_contents():
    """Key positions past pos0 + t (not yet written at chunk position t in
    the stepwise order) and the trash block must never leak into the
    output, whatever garbage they hold."""
    b, hq, hkv, d, bs, c = 2, 4, 2, 16, 4, 4
    pos0, n_live = (2, 5), (4, 3)
    q, kp, vp, tables, p0 = _prefill_case(KEY, b, hq, hkv, d, bs, c, pos0)
    nl = jnp.asarray(np.asarray(n_live, np.int32))
    out0 = ops.paged_prefill(q, kp, vp, tables, p0, nl, interpret=True)
    pk, pv = kp.at[0].set(1e9), vp.at[0].set(-1e9)       # trash block
    # poison every pool position past each slot's last live key
    tb = np.asarray(tables)
    for i, (p, n) in enumerate(zip(pos0, n_live)):
        for pos in range(p + n, tb.shape[1] * bs):
            blk, off = tb[i, pos // bs], pos % bs
            pk = pk.at[blk, :, off].set(1e9)
            pv = pv.at[blk, :, off].set(-1e9)
    out1 = ops.paged_prefill(q, pk, pv, tables, p0, nl, interpret=True)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1))
    ref0 = ref.paged_prefill_ref(q.swapaxes(1, 2), kp, vp, tables, p0, nl)
    ref1 = ref.paged_prefill_ref(q.swapaxes(1, 2), pk, pv, tables, p0, nl)
    np.testing.assert_allclose(np.asarray(ref0), np.asarray(ref1))


# --------------------------------------------------------------------------- #
# rglru scan
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,s,r,bs", [
    (1, 64, 32, 16), (2, 100, 96, 32), (3, 256, 128, 256), (1, 8, 16, 8),
])
def test_rglru_scan_matches_ref(b, s, r, bs):
    k1, k2, k3 = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (b, s, r)))
    bb = jax.random.normal(k2, (b, s, r))
    h0 = jax.random.normal(k3, (b, r))
    y, hn = ops.rglru_scan(a, bb, h0, bs=bs, interpret=True)
    yr, hnr = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hn), np.asarray(hnr), atol=1e-5,
                               rtol=1e-4)


def test_rglru_scan_zero_init():
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(jax.random.normal(k1, (2, 32, 16)))
    bb = jax.random.normal(k2, (2, 32, 16))
    y, hn = ops.rglru_scan(a, bb, None, bs=8, interpret=True)
    yr, hnr = ref.rglru_scan_ref(a, bb, jnp.zeros((2, 16)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5,
                               rtol=1e-4)


# --------------------------------------------------------------------------- #
# rwkv6 scan
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,s,h,n,chunk", [
    (1, 64, 2, 16, 16), (2, 96, 3, 32, 32), (1, 40, 1, 8, 16),
])
def test_rwkv6_scan_matches_ref(b, s, h, n, chunk):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, n))) * 0.7 + 0.29
    u = jax.random.normal(ks[4], (h, n))
    s0 = jax.random.normal(ks[5], (b, h, n, n))
    y, sn = ops.rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    yr, snr = ref.rwkv6_scan_ref(r.swapaxes(1, 2), k.swapaxes(1, 2),
                                 v.swapaxes(1, 2), w.swapaxes(1, 2), u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr.swapaxes(1, 2)),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sn), np.asarray(snr), atol=2e-4,
                               rtol=1e-3)


def test_rwkv6_model_chunked_matches_sequential_oracle():
    """The model's chunked-parallel WKV == the kernel's sequential oracle."""
    from repro.models.rwkv6 import wkv6_chunked_ref
    ks = jax.random.split(KEY, 6)
    b, s, h, n = 2, 64, 2, 16
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, n))) * 0.7 + 0.29
    u = jax.random.normal(ks[4], (h, n))
    s0 = jax.random.normal(ks[5], (b, h, n, n))
    y, sn = wkv6_chunked_ref(r, k, v, w, u, s0, chunk=16)
    yr, snr = ref.rwkv6_scan_ref(r.swapaxes(1, 2), k.swapaxes(1, 2),
                                 v.swapaxes(1, 2), w.swapaxes(1, 2), u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr.swapaxes(1, 2)),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sn), np.asarray(snr), atol=2e-4,
                               rtol=1e-3)


# --------------------------------------------------------------------------- #
# paged-KV block copy (the prefix cache's copy-on-write primitive)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("axis", [0, 2])
def test_copy_blocks_copies_listed_rows_only(axis):
    """copy_blocks must replicate exactly the src rows onto the dst rows
    along the given axis — other rows untouched, sources unmodified, and
    (0, 0) padding pairs must be no-ops."""
    shape = [5, 3, 6, 2]
    x = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    src = jnp.asarray([2, 4, 0], jnp.int32)     # last pair: (0, 0) pad
    dst = jnp.asarray([1, 3, 0], jnp.int32)
    got = np.asarray(ops.copy_blocks(jnp.asarray(x), src, dst, axis=axis))
    want = x.copy()
    mv = np.moveaxis(want, axis, 0)
    mv[1] = np.moveaxis(x, axis, 0)[2]
    mv[3] = np.moveaxis(x, axis, 0)[4]
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------- #
# int8 KV block quantization (ADR-009 compressed disagg handoff)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 8, 2, 16), (3, 6, 4), (5, 12)])
def test_quantize_kv_blocks_matches_ref(shape, dtype):
    """Device quantize must match the loop-form oracle bit-for-bit on the
    int8 payload (scales/dequant to 1 ulp), and the round trip must stay
    within half a quantization step of the original per (block, head)."""
    blocks = _rand(KEY, shape, dtype) * 3.0
    q, scales = ops.quantize_kv_blocks(blocks)
    qr, sr = ref.quantize_kv_blocks_ref(blocks)
    assert q.dtype == jnp.int8 and scales.dtype == jnp.float32
    # keepdims: scales broadcast against blocks, one per (block, head);
    # head-major blocks (n, Hkv, bs, D) carry the head axis at -3.
    assert scales.ndim == blocks.ndim and scales.shape[0] == shape[0]
    want_scale_shape = tuple(
        n if i == 0 or (len(shape) >= 4 and i == len(shape) - 3) else 1
        for i, n in enumerate(shape))
    assert scales.shape == want_scale_shape
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(sr),
                               rtol=1e-6, atol=0)
    deq = ops.dequantize_kv_blocks(q, scales, dtype=dtype)
    deqr = ref.dequantize_kv_blocks_ref(qr, sr, dtype=dtype)
    assert deq.dtype == dtype
    # scales may differ by 1 ulp between the jnp and numpy paths, so the
    # dequantized payload is allclose-tight rather than bit-exact.
    np.testing.assert_allclose(np.asarray(deq.astype(jnp.float32)),
                               np.asarray(deqr.astype(jnp.float32)),
                               rtol=1e-6, atol=1e-6)
    # |x - deq(q(x))| <= scale/2 elementwise (round-to-nearest bound).
    err = np.abs(np.asarray(blocks, np.float32)
                 - np.asarray(deq, np.float32))
    bound = np.broadcast_to(np.asarray(scales), shape) * 0.5 + 1e-6
    if dtype == jnp.bfloat16:      # input itself only has ~8 mantissa bits
        bound = bound + 0.02 * np.abs(np.asarray(blocks, np.float32))
    assert np.all(err <= bound)


def test_quantize_kv_blocks_range_and_zeros():
    """Payload must use the full symmetric int8 range and map all-zero
    blocks to exact zeros (the 1e-12 scale floor must not inject noise)."""
    blocks = jnp.stack([jnp.full((4, 2, 8), 0.0, jnp.float32),
                        jnp.full((4, 2, 8), 5.0, jnp.float32)])
    q, scales = ops.quantize_kv_blocks(blocks)
    assert int(jnp.max(jnp.abs(q))) == 127
    np.testing.assert_array_equal(np.asarray(q[0]), 0)
    deq = ops.dequantize_kv_blocks(q, scales, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(deq[0]), 0.0)
    np.testing.assert_allclose(np.asarray(deq[1]), 5.0, rtol=1e-5)


# --------------------------------------------------------------------------- #
# the platform decision (one place: ops.serving_impl / ops._interpret)
# --------------------------------------------------------------------------- #
def test_cpu_serves_xla_and_runs_kernels_only_when_asked():
    """On the CPU the serving stack takes the XLA path, and a kernel runs
    in the Pallas interpreter only when its caller asks: never a silent
    interpreter standing in for the chip."""
    assert jax.default_backend() == "cpu"
    assert ops.serving_impl() == "xla"
    q, kp, vp, tables, cls = _paged_case(KEY, 2, 4, 2, 16, 4, (3, 7))
    with pytest.raises(RuntimeError, match="only for the TPU"):
        ops.paged_attention(q[:, None], kp, vp, tables, cls)
    out = ops.paged_attention(q[:, None], kp, vp, tables, cls,
                              interpret=True)
    assert out.shape == (2, 1, 4, 16)
