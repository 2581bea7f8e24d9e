"""Hypothesis property tests on system invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (pip install -e .[dev])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.clones import resume_time
from repro.core.policy import Policy, Prediction, should_offload
from repro.core.profilers import size_bucket
from repro.core.parallel import split_batch, split_range
from repro.distributed.compression import dequantize_int8, quantize_int8

TIMES = st.floats(min_value=1e-6, max_value=1e6, allow_nan=False)


@given(tl=TIMES, el=TIMES, tr=TIMES, er=TIMES)
def test_policy_offload_implies_improvement(tl, el, tr, er):
    """Under any single-objective policy, offloading implies that objective
    strictly improves (the paper's definition)."""
    local, remote = Prediction(tl, el), Prediction(tr, er)
    if should_offload(Policy.EXEC_TIME, local, remote):
        assert remote.time_s < local.time_s
    if should_offload(Policy.ENERGY, local, remote):
        assert remote.energy_j < local.energy_j
    if should_offload(Policy.EXEC_TIME_AND_ENERGY, local, remote):
        assert remote.time_s < local.time_s
        assert remote.energy_j < local.energy_j
    assert not should_offload(Policy.NONE, local, remote)


@given(st.integers(min_value=1, max_value=64))
def test_resume_time_monotone_in_contention(k):
    assert resume_time(k + 1) > resume_time(k)
    assert resume_time(1) == 0.300


@given(st.floats(min_value=1.0, max_value=1e12))
def test_size_bucket_monotone(n):
    assert size_bucket(2 * n) >= size_bucket(n)


@given(st.integers(min_value=1, max_value=257), st.integers(1, 8))
def test_split_batch_roundtrip(n, k):
    x = np.arange(n, dtype=np.int64)
    shards = split_batch((x,), k)
    merged = np.concatenate([s[0] for s in shards])
    np.testing.assert_array_equal(merged, x)


@given(st.integers(0, 100), st.integers(1, 1000), st.integers(1, 16))
def test_split_range_covers_exactly(lo, width, k):
    hi = lo + width
    parts = split_range(lo, hi, k)
    assert parts[0][0] == lo and parts[-1][1] == hi
    for (a, b), (c, d) in zip(parts, parts[1:]):
        assert b == c and a <= b and c <= d


@settings(deadline=None, max_examples=25)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from([2, 4, 8]),
       st.lists(st.integers(min_value=1, max_value=24), min_size=1,
                max_size=4))
def test_block_table_gather_matches_contiguous_cache(seed, bs, lens):
    """For any block size, context lengths, and (shuffled) physical block
    placement, attention through a block-table gather equals attention over
    the same KV stored contiguously — the invariant that makes paged decode
    token-identical to the contiguous cohort cache."""
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    b, hq, hkv, d = len(lens), 4, 2, 16
    max_blk = max(-(-ln // bs) for ln in lens)
    n_blocks = sum(-(-ln // bs) for ln in lens) + 1
    kp = rng.standard_normal((n_blocks, hkv, bs, d)).astype(np.float32)
    vp = rng.standard_normal((n_blocks, hkv, bs, d)).astype(np.float32)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    perm = list(rng.permutation(np.arange(1, n_blocks)))   # scattered blocks
    tables = np.zeros((b, max_blk), np.int32)
    for i, ln in enumerate(lens):
        for j in range(-(-ln // bs)):
            tables[i, j] = perm.pop()
    got = np.asarray(ref.paged_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(np.asarray(lens, np.int32))))
    for i, ln in enumerate(lens):
        nb = -(-ln // bs)
        # head-major blocks (nb, Hkv, bs, D) -> contiguous (nb*bs, Hkv, D)
        kc = kp[tables[i, :nb]].swapaxes(1, 2).reshape(-1, hkv, d)[:ln]
        vc = vp[tables[i, :nb]].swapaxes(1, 2).reshape(-1, hkv, d)[:ln]
        want = np.asarray(ref.flash_attention_ref(
            jnp.asarray(q[i:i + 1, :, None]),
            jnp.swapaxes(jnp.asarray(kc[None]), 1, 2),
            jnp.swapaxes(jnp.asarray(vc[None]), 1, 2),
            causal=False))[:, :, 0]
        np.testing.assert_allclose(got[i:i + 1], want, atol=2e-5, rtol=2e-5)


class _AllocStubBackend:
    """Minimal backend for host-side allocator properties (no device)."""

    capacity = 32

    def init_paged_pool(self, max_slots, num_blocks, block_size):
        return {}


def _alloc_invariants(kv):
    """The refcounted-allocator safety net (ADR-003): every physical
    block is in exactly one of {free, cached-free, referenced}; refcounts
    equal the number of block-table references; the trash block and the
    cached-free list stay clean."""
    n = kv.num_blocks
    refcalc = np.zeros(n, np.int64)
    for s in range(kv.max_slots):
        for j in range(int(kv.n_blocks_of[s])):
            refcalc[int(kv.tables[s, j])] += 1
    assert (refcalc == np.asarray(kv.ref, np.int64)).all(), \
        "refcounts must equal the number of tables referencing each block"
    free = set(kv._free_blocks)
    cached = set(kv._cached_free)
    refd = {b for b in range(1, n) if kv.ref[b] > 0}
    assert len(kv._free_blocks) == len(free), "double-free: dup free list"
    assert not free & cached and not free & refd and not cached & refd
    assert free | cached | refd == set(range(1, n)), "leaked block"
    assert 0 not in free and 0 not in cached and kv.ref[0] == 0
    assert all(b in kv._node for b in cached), "cached-free must be indexed"


@settings(deadline=None, max_examples=40)
@given(st.integers(0, 2 ** 31 - 1),
       st.lists(st.tuples(st.integers(0, 3), st.integers(0, 15)),
                min_size=1, max_size=40))
def test_refcounted_allocator_never_leaks_or_double_frees(seed, ops):
    """For any interleaving of admit / decode-grow / free / preempt (and
    the round-boundary pending clear), the refcounted prefix-cache
    allocator never leaks a block, never double-frees, and every shared
    block's refcount equals the number of tables referencing it — under
    heavy prefix overlap, CoW splits, LRU eviction, and exhaustion."""
    from repro.launch.serve import KVBlockPool, PoolExhausted
    kv = KVBlockPool(_AllocStubBackend(), max_slots=3, block_size=4,
                     num_blocks=12)
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 7, 32).astype(np.int32)   # common ancestor
    live = []
    for kind, x in ops:
        if kind == 0 and kv.free_slots:              # admit
            pl = 1 + x % 14
            prompt = base[:pl].copy()
            if x % 3 == 0:                           # diverge the tail
                prompt[-1] = 90 + x % 4
            if kv.can_admit(prompt, x % 8):
                slot, _, _, _ = kv.alloc_slot(prompt, x % 8,
                                              force_suffix=x % 5 == 0)
                kv.active[slot] = True
                live.append(slot)
        elif kind == 1 and live:                     # decode growth
            counts = np.zeros((kv.max_slots,), np.int32)
            for s in live:
                counts[s] = 1 + x % 4
            try:
                kv.grow_for_window(counts)
                kv.pos[live] = np.minimum(kv.pos[live] + counts[live],
                                          kv.capacity)
            except PoolExhausted:
                pass                                 # engine would preempt
        elif kind == 2 and live:                     # retire/preempt/cancel
            slot = live.pop(x % len(live))
            if x % 2:
                kv.free_slot(slot)
            else:
                kv.cancel_slot(slot)
        else:                                        # round boundary
            kv.clear_pending()
        _alloc_invariants(kv)
    for slot in list(live):
        kv.free_slot(slot)
    _alloc_invariants(kv)
    assert not np.asarray(kv.ref).any()              # all refs returned


class _DecodeLoopRig:
    """Shared tiny model + paged decode state for the decode_loop property.

    Built once (module scope) so hypothesis examples only re-run the cheap
    decode calls; all shapes are fixed across examples, so the jitted
    decode_step / decode_loop compile exactly once each.
    """

    SLOTS, BLOCK, CAP, T = 3, 4, 16, 4

    def __init__(self):
        import test_models as tm      # sibling module (pytest sys.path)
        from repro.configs import get_config, reduced_config
        from repro.models import model
        from repro.models.context import RunContext
        self.model = model
        self.cfg = reduced_config(get_config("smollm-360m"))
        self.ctx = RunContext()
        self.params = model.init(self.cfg, jnp.asarray([0, 5],
                                                       dtype=jnp.uint32))
        self.cache, self.tables, self.tok, self.pos = tm._paged_decode_state(
            self.cfg, self.ctx, self.params, prompt_lens=[3, 5, 2],
            block_size=self.BLOCK, capacity=self.CAP)
        self._stepwise = tm._stepwise_decode

    def run(self, budgets, warmup):
        """Advance each slot ``warmup`` extra tokens (randomizing cursors),
        then compare decode_loop vs stepwise over ``budgets``."""
        import jax
        cache = jax.tree.map(jnp.copy, self.cache)
        warm = np.asarray(warmup, np.int32)
        tok, pos = self.tok, self.pos
        if warm.max() > 0:
            out, cache = self._stepwise(self.cfg, self.ctx, self.params,
                                        cache, self.tables, tok, pos, warm,
                                        self.BLOCK, self.CAP,
                                        int(warm.max()))
            rows = np.arange(self.SLOTS)
            tok = np.where(warm > 0, out[rows, warm - 1],
                           tok[:, 0])[:, None].astype(np.int32)
            pos = np.minimum(pos + warm, self.CAP)
        budgets = np.asarray(budgets, np.int32)
        want, _ = self._stepwise(self.cfg, self.ctx, self.params,
                                 jax.tree.map(jnp.copy, cache), self.tables,
                                 tok, pos, budgets, self.BLOCK, self.CAP,
                                 self.T)
        got, _ = self.model.decode_loop(
            self.cfg, self.params, cache, jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(budgets), self.ctx,
            block_tables=jnp.asarray(self.tables), block_size=self.BLOCK,
            num_steps=self.T, capacity=self.CAP)
        np.testing.assert_array_equal(np.asarray(got), want)


_RIG = []


@settings(deadline=None, max_examples=12)
@given(st.lists(st.integers(0, _DecodeLoopRig.T), min_size=3, max_size=3),
       st.lists(st.integers(0, 3), min_size=3, max_size=3))
def test_decode_loop_token_identical_to_stepwise(budgets, warmup):
    """For any slot occupancy (budget 0 = empty slot), cursor offsets, and
    mid-window completions (budget < T), one decode_loop dispatch emits
    exactly the tokens of T host-driven decode_step dispatches — the
    invariant that lets the serving layer fuse T tokens per round-trip."""
    if not _RIG:
        _RIG.append(_DecodeLoopRig())
    _RIG[0].run(budgets, warmup)


@settings(deadline=None)
@given(st.lists(st.floats(min_value=-1e4, max_value=1e4,
                          allow_nan=False, width=32),
                min_size=1, max_size=64))
def test_int8_quantization_error_bound(xs):
    """|x - deq(quant(x))| <= scale/2 + eps elementwise."""
    g = jnp.asarray(xs, jnp.float32)
    q, scale = quantize_int8(g)
    err = np.abs(np.asarray(g) - np.asarray(dequantize_int8(q, scale)))
    assert err.max() <= float(scale) / 2 + 1e-5 + float(scale) * 1e-3


@given(st.integers(2, 64), st.integers(1, 63))
def test_spec_divisibility_fallback(dim_mult, off):
    """spec_for never assigns a mesh axis that does not divide the dim."""
    import jax
    from repro.distributed.sharding import spec_for
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    # model axis size 1 divides everything -> sharding allowed
    spec = spec_for((dim_mult,), ("mlp",), mesh)
    assert spec == jax.sharding.PartitionSpec("model") or \
        spec == jax.sharding.PartitionSpec()


@settings(deadline=None)
@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 3))
def test_energy_additivity(util10, bright16, secs):
    """PowerTutor components are independent: total = sum of parts."""
    from repro.core.energy import PhoneState, PowerTutorModel
    m = PowerTutorModel()
    st_ = PhoneState(cpu_util=util10 * 10.0, brightness=bright16 * 16)
    total = sum(m.energy_j(st_, float(secs)).values())
    parts = m.power_mw(st_)
    assert total == sum(v * 1e-3 * secs for v in parts.values())


# ---------------------------------------------------------------------------
# chunked paged prefill (ADR-005)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 996),
       prefix_lens=st.lists(st.integers(0, 8), min_size=3, max_size=3),
       n_tok=st.lists(st.integers(0, 8), min_size=3, max_size=3),
       chunk=st.sampled_from([1, 2, 3, 4, 8]))
def test_chunked_prefill_token_identical_to_stepwise(seed, prefix_lens,
                                                     n_tok, chunk):
    """ADR-005 property: for any per-row prefix/suffix lengths and any
    chunk size, the chunked suffix scan returns the stepwise scan's first
    tokens and leaves every live pool block equal up to float32 reduction
    order (``test_models.CHUNK_KV_ATOL``).  (The
    deterministic twin lives in test_models.py so the invariant is still
    exercised where hypothesis is not installed.)"""
    import test_models as tm
    tm._check_chunked_vs_stepwise(prefix_lens, n_tok, chunk, seed=seed)


# ---------------------------------------------------------------------------
# fault-injected serving (ADR-006)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 7),
       hedge=st.sampled_from([0.0, 2.0]),
       faults=st.lists(
           st.tuples(st.floats(min_value=0.05, max_value=0.95),
                     st.sampled_from(["kill", "drain", "slow"]),
                     st.sampled_from([0.0, 0.5, 2.0]),
                     st.sampled_from([4.0, 40.0])),
           min_size=1, max_size=3))
def test_fault_recovery_conserves_requests_and_blocks(seed, hedge, faults):
    """ADR-006 property: for any schedule of kill/drain/slow faults (any
    times, durations, slowdown factors, hedging on or off), the handler
    loses no request, leaks no KV block, emits tokens bit-identical to
    the faultless run, and keeps its recovery counters consistent.
    (``run_chaos_trace`` asserts block conservation internally; its
    deterministic twin lives in test_faults.py so the invariant is still
    exercised where hypothesis is not installed.)"""
    import test_faults as tf
    from repro.core.faults import CloneFault
    base = tf.run_chaos_trace(seed=seed)
    span = base["makespan_s"]
    sched = [CloneFault(at=frac * span, kind=kind, duration=dur,
                        factor=factor)
             for frac, kind, dur, factor in faults]
    out = tf.run_chaos_trace(sched, hedge=hedge, seed=seed)
    assert out["served"] == out["offered"] == base["served"]   # none lost
    assert out["tokens"] == base["tokens"]     # recovery is latency-only
    assert out["injected"] <= len(sched)
    assert out["hedge_wins"] <= out["hedges_fired"]
    if hedge == 0.0:
        assert out["hedges_fired"] == 0
    if not any(k == "drain" for _, k, _, _ in faults):
        assert out["migrated"] == 0            # only drains salvage KV


@settings(deadline=None, max_examples=15)
@given(seed=st.integers(0, 2 ** 31 - 1),
       adv_weight=st.floats(min_value=0.25, max_value=8.0,
                            allow_nan=False),
       adv_cost=st.sampled_from([1, 2, 4, 8]),
       adv_n=st.integers(10, 80),
       rate=st.sampled_from([2.0, 4.0, 8.0, 16.0]),
       burst=st.sampled_from([2.0, 8.0, 16.0]))
def test_gateway_quota_and_fair_share(seed, adv_weight, adv_cost, adv_n,
                                      rate, burst):
    """ADR-007 property: under any adversarial arrival mix — a flooding
    tenant of arbitrary weight/cost/volume against a steady victim and a
    token-bucket-metered tenant — the gateway's release schedule never
    lets the metered tenant exceed ``burst + rate x t`` cumulative
    tokens, and the victim's weight-normalized share stays within a
    DRR-granularity bound (it is never starved).  The deterministic twin
    lives in test_gateway.py (``check_quota_invariants``)."""
    import test_gateway as tg
    tg.check_quota_invariants(tg.run_quota_trace(
        adv_weight=adv_weight, adv_cost=adv_cost, adv_n=adv_n,
        rate=rate, burst=burst, seed=seed))


# ---------------------------------------------------------------------------
# cross-tier speculative decoding (ADR-008)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 996),
       prompt_lens=st.lists(st.integers(1, 8), min_size=2, max_size=3),
       budgets=st.lists(st.integers(0, 7), min_size=3, max_size=3),
       k_max=st.integers(1, 4),
       flip_p=st.sampled_from([0.0, 0.3, 0.6, 1.0]))
def test_speculative_decode_token_identical_to_stepwise(seed, prompt_lens,
                                                        budgets, k_max,
                                                        flip_p):
    """ADR-008 property: for any draft-agreement pattern — random per-row
    per-round window sizes K, mid-window rejections (proposals corrupted
    with probability ``flip_p``), dead rows (budget 0), ragged budgets —
    the draft_loop + verify_window rounds emit a stream bitwise identical
    to stepwise greedy decode, and the committed KV they leave behind is
    indistinguishable under continuation.  (The deterministic twin lives
    in test_models.py so the invariant is still exercised where
    hypothesis is not installed.)"""
    import test_models as tm
    tm._check_spec_vs_stepwise(prompt_lens + [1] * (3 - len(prompt_lens)),
                               budgets, k_max, flip_p, seed=seed)


@settings(deadline=None, max_examples=5)
@given(seed=st.integers(0, 2 ** 31 - 1), chunk=st.sampled_from([2, 4, 8]))
def test_chunked_serving_preemption_invariant(seed, chunk):
    """ADR-005 property: serving a seeded shared-prefix trace on a tight
    pool — mid-stream preemptions, restores, prefix hits — is observably
    invariant to prefill chunking: identical per-request tokens and
    identical KVBlockPool refcount economics (preemption / restored /
    prefix-hit counters)."""
    import test_handler as th
    assert th._run_tight_chunk_trace(seed, 0, False) == \
        th._run_tight_chunk_trace(seed, chunk, True)


# ---------------------------------------------------------------------------
# disaggregated prefill/decode + affinity routing (ADR-009)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=6)
@given(seed=st.integers(0, 2 ** 31 - 1),
       routing=st.sampled_from(["ledger", "affinity", "random"]),
       compress=st.booleans())
def test_disagg_affinity_conserves_blocks_and_tokens(seed, routing,
                                                     compress):
    """ADR-009 property: for any seeded shared-prefix trace, routing
    mode, and compression setting, disaggregated serving (partner
    prefill + cross-clone paged-KV migration) loses no request, leaks
    and double-frees no block in any per-clone or partner scratch pool
    (asserted inside the helper), always hands off at least one cold
    prompt, and — compression off — emits streams bitwise identical to
    the co-located ledger-routed greedy baseline.  (The deterministic
    twin lives in test_handler.py so the invariant is still exercised
    where hypothesis is not installed.)"""
    import test_handler as th
    base = th.run_disagg_affinity_trace(seed)
    out = th.run_disagg_affinity_trace(seed, routing=routing,
                                       disagg=True, compress=compress)
    assert out["served"] == out["offered"] == base["served"]
    assert out["handoffs"] >= 1
    assert out["xfer_bytes"] > 0
    if not compress:
        assert out["tokens"] == base["tokens"]
