"""Event-driven Client Handler subsystem: virtual clock, dispatcher overlap,
parallel makespan on the timeline, elastic autoscaling, and continuous
batching equivalence.  Everything here is deterministic — no real sleeps."""
import time

import numpy as np
import pytest

from repro.core import (ClonePool, Dispatcher, ExecutionController,
                        Parallelizer, Policy, RemoteableMethod, VirtualClock,
                        split_batch)
from repro.core.clones import BOOT_SECONDS, CloneState, resume_time
from repro.core.parallel import SYNC_SECONDS_PER_CLONE
from repro.core.scheduler import (AdmissionQueue, ServeRequest, SlotLedger,
                                  poisson_arrivals)


# --------------------------------------------------------------------------- #
# virtual clock
# --------------------------------------------------------------------------- #
def test_virtual_clock_fires_events_in_order():
    clk = VirtualClock()
    fired = []
    clk.schedule(2.0, lambda: fired.append("b"))
    clk.schedule(1.0, lambda: fired.append("a"))
    clk.schedule(3.0, lambda: fired.append("c"))
    clk.advance_to(2.5)
    assert fired == ["a", "b"]
    assert clk.now() == 2.5
    clk.sleep(1.0)
    assert fired == ["a", "b", "c"]


def test_virtual_clock_rejects_time_travel():
    clk = VirtualClock(start=5.0)
    with pytest.raises(ValueError):
        clk.advance_to(1.0)
    with pytest.raises(ValueError):
        clk.at(1.0)


def test_virtual_clock_cancel_and_run_next():
    clk = VirtualClock()
    fired = []
    ev = clk.schedule(1.0, lambda: fired.append("x"))
    clk.schedule(2.0, lambda: fired.append("y"))
    ev.cancel()
    assert clk.run_next()
    assert fired == ["y"] and clk.now() == 2.0
    assert not clk.run_next()


# --------------------------------------------------------------------------- #
# dispatcher: k submissions overlap on the timeline
# --------------------------------------------------------------------------- #
def _fixed_executor(seconds_by_call):
    calls = {"n": 0}

    def ex(clone, fn, args):
        dt = seconds_by_call[min(calls["n"], len(seconds_by_call) - 1)]
        calls["n"] += 1
        return fn(*args), dt

    return ex


def test_dispatcher_overlap_is_max_not_sum():
    clk = VirtualClock()
    pool = ClonePool(clock=clk)
    clones = pool.provision("main", 3, state=CloneState.RUNNING)
    disp = Dispatcher(pool, clk)
    ex = _fixed_executor([1.0, 2.0, 3.0])
    tasks = [disp.submit(c, lambda v=i: v, (), executor=ex)
             for i, c in enumerate(clones)]
    disp.wait(tasks)
    assert clk.now() == pytest.approx(3.0)       # max, not 6.0
    assert [t.value for t in tasks] == [0, 1, 2]
    assert all(t.done for t in tasks)


def test_dispatcher_requires_virtual_clock():
    pool = ClonePool(clock=lambda: 0.0)
    with pytest.raises(TypeError):
        Dispatcher(pool, pool.clock)


# --------------------------------------------------------------------------- #
# parallelizer on the virtual timeline
# --------------------------------------------------------------------------- #
def test_parallel_makespan_is_provision_plus_max_plus_sync():
    """Acceptance: k-clone makespan within 10% of provision + max + sync,
    with zero real sleeping on the simulated path."""
    pool = ClonePool()                          # VirtualClock by default
    pool.provision("main", 4)                   # paused secondaries
    par = Parallelizer(pool)
    shard_times = {0: 1.0, 1: 2.0, 2: 4.0, 3: 3.0}

    def venue_executor(clone, fn, shard):
        i = int(shard[0])
        return i, shard_times[i]

    wall0 = time.perf_counter()
    res = par.run(lambda i: i, [(i,) for i in range(4)],
                  venue_executor=venue_executor, merge=sum)
    wall = time.perf_counter() - wall0
    # primary is RUNNING, 3 paused clones resume simultaneously
    expected = resume_time(3) + 4.0 + SYNC_SECONDS_PER_CLONE * 3
    assert res.makespan_s == pytest.approx(expected, rel=0.10)
    assert res.makespan_s == pytest.approx(expected, rel=1e-6)
    assert max(res.shard_times) == pytest.approx(4.0)
    assert res.value == 0 + 1 + 2 + 3
    assert wall < 1.0                           # simulated, not slept


def test_straggler_detected_at_event_time():
    pool = ClonePool()
    pool.provision("main", 6)
    par = Parallelizer(pool, straggler_factor=2.0)
    seen = {"rescues": 0}

    def venue_executor(clone, fn, shard):
        i = int(shard[0])
        if i == 3 and seen["rescues"] == 0:
            seen["rescues"] += 1
            return i, 50.0                      # straggling first attempt
        return i, 1.0

    res = par.run(lambda i: i, [(i,) for i in range(4)],
                  venue_executor=venue_executor, merge=list)
    assert res.redispatches == 1
    # detection at 2 x median(=1.0) => rescue lands at ~2 + resume + 1
    assert max(res.shard_times) == pytest.approx(
        2.0 + resume_time(1) + 1.0, rel=1e-6)
    assert res.value == [0, 1, 2, 3]


# --------------------------------------------------------------------------- #
# clone pool accounting (satellite regressions)
# --------------------------------------------------------------------------- #
def test_boot_seconds_counted_per_clone():
    pool = ClonePool()
    pool.acquire("x4large", n=3)                # three cold boots
    assert pool.stats["boots"] == 3
    assert pool.stats["boot_seconds"] == pytest.approx(3 * BOOT_SECONDS)


def test_ensure_secondaries_and_pause_surplus():
    pool = ClonePool()
    pool.provision("main", 2)                   # paused
    fresh, costs = pool.ensure_secondaries("main", 3)
    assert len(fresh) == 3                      # 2 resumed + 1 booted
    # per-clone readiness: resumed clones don't wait for the boot
    assert costs == pytest.approx([resume_time(2), resume_time(2),
                                   BOOT_SECONDS])
    assert len(pool.running_secondaries("main")) == 3
    assert all(not c.busy for c in fresh)       # idle capacity, not acquired
    assert pool.pause_surplus(keep=1, type_name="main") == 2
    assert len(pool.running_secondaries("main")) == 1


def test_parallel_run_feeds_network_profiler():
    """Multi-clone runs must update bandwidth/RTT history like single-clone
    runs, or later offload predictions go stale."""
    ec = ExecutionController(policy=Policy.EXEC_TIME)
    ec.pool.provision("main", 4, state=CloneState.RUNNING)
    rm = RemoteableMethod(
        "par", lambda xs: xs.sum(), size_fn=lambda xs: xs.size,
        split_fn=lambda args, k: split_batch(args, k),
        merge_fn=lambda vs: sum(float(v) for v in vs))
    ec.execute(rm, np.ones((8, 16), np.float32), force="remote", n_clones=4)
    assert ec.network.perceived_bw.get(ec.network.active)
    assert ec.network.perceived_rtt.get(ec.network.active)


# --------------------------------------------------------------------------- #
# admission queue
# --------------------------------------------------------------------------- #
def test_admission_queue_sheds_beyond_depth():
    q = AdmissionQueue(max_depth=2)
    reqs = [ServeRequest(i, np.zeros(4, np.int32)) for i in range(5)]
    admitted = [q.offer(r, now=0.0) for r in reqs]
    assert admitted == [True, True, False, False, False]
    assert q.rejected == 3
    assert [r.rid for r in q.take(10)] == [0, 1]


def test_slot_ledger_fills_tightest_engine_first():
    q = AdmissionQueue()
    for i in range(4):
        q.offer(ServeRequest(i, np.zeros(4, np.int32)), now=0.0)
    led = SlotLedger()
    led.update("a", 3)
    led.update("b", 1)
    assert led.total_free == 4
    picks = led.assign(q)
    # tightest engine (b, 1 free) is refilled before the emptier one
    assert [(k, r.rid) for k, r in picks] == \
        [("b", 0), ("a", 1), ("a", 2), ("a", 3)]
    assert led.total_free == 0 and q.depth == 0


def test_slot_ledger_drop_and_zero_update():
    led = SlotLedger()
    led.update("a", 2)
    led.update("a", 0)          # engine filled up -> forgotten
    led.update("b", 1)
    led.drop("b")
    q = AdmissionQueue()
    q.offer(ServeRequest(0, np.zeros(4, np.int32)), now=0.0)
    assert led.assign(q) == [] and q.depth == 1


def test_kv_block_pool_alloc_grow_free():
    from repro.launch.serve import KVBlockPool
    kv = KVBlockPool(FakeBackend(), max_slots=2, block_size=4)
    assert kv.max_blk == 16                     # capacity 64 / bs 4
    slot, ids, cached, cow = kv.alloc_slot(6, max_new_tokens=6)
    assert len(ids) == 2 and 0 not in ids       # trash block never handed out
    assert cached == 0 and cow is None          # bare length: no matching
    assert kv.pos[slot] == 6 and kv.used_blocks() == 2
    assert all(kv.ref[i] == 1 for i in ids)     # private blocks: refcount 1
    kv.active[slot] = True
    kv.pos[slot] = 8                            # cursor hits block boundary
    kv.grow_for_write()                         # next write needs block 3
    assert kv.n_blocks_of[slot] == 3 and kv.used_blocks() == 3
    kv.free_slot(slot)
    assert kv.used_blocks() == 0 and kv.free_slots == 2
    assert not kv.ref.any()                     # every refcount back to zero
    assert not kv.tables.any()                  # table rows reset to trash


def test_kv_block_pool_prefix_sharing_refcounts_and_cow():
    """Two prompts sharing a full token block map it at refcount 2; the
    first divergent block is claimed fresh with a copy-on-write source;
    freeing a sharer decrements, and the block only recirculates (via the
    cached-free list) at refcount zero."""
    from repro.launch.serve import KVBlockPool
    kv = KVBlockPool(FakeBackend(), max_slots=3, block_size=4)
    pa = np.array([7, 7, 7, 7, 1, 2, 3, 4, 9], np.int32)   # 2 full blocks
    pb = np.array([7, 7, 7, 7, 1, 2, 3, 5, 9], np.int32)   # diverges in b1
    sa, ids_a, ca, cow_a = kv.alloc_slot(pa, 4)
    assert ca == 0 and cow_a is None and len(ids_a) == 3
    sb, ids_b, cb, cow_b = kv.alloc_slot(pb, 4)
    assert cb == 4 + 3                          # full block + CoW partial
    assert kv.tables[sb, 0] == kv.tables[sa, 0]  # block 0 shared
    assert kv.ref[kv.tables[sa, 0]] == 2
    assert cow_b is not None
    assert cow_b[0] == kv.tables[sa, 1]          # CoW source: a's block 1
    assert cow_b[1] == kv.tables[sb, 1] != kv.tables[sa, 1]  # fresh copy
    kv.free_slot(sa)
    assert kv.ref[kv.tables[sb, 0]] == 1         # b still holds the share
    kv.free_slot(sb)
    assert not kv.ref.any()
    # the indexed prompt blocks stay cached-free: a re-admission of the
    # same prompt resurrects both full blocks and only allocates the
    # partial tail block (position 8 — never indexed, not full)
    free_before = kv.available_blocks()
    sc, ids_c, cc, cow_c = kv.alloc_slot(pa, 4)
    assert cc == 8 and len(ids_c) == 1 and cow_c is None
    assert kv.ref[kv.tables[sc, 0]] == 1 and kv.ref[kv.tables[sc, 1]] == 1
    # 2 resurrected from cached-free + 1 fresh: all now referenced
    assert kv.available_blocks() == free_before - 3


def test_kv_block_pool_cancel_unindexes_unwritten_blocks():
    """A cancelled admission (join rollback) must remove the trie nodes
    it created: their device content was never written, so matching them
    later would serve garbage KV.  A normally-freed slot's nodes stay."""
    from repro.launch.serve import KVBlockPool
    kv = KVBlockPool(FakeBackend(), max_slots=2, block_size=4)
    pa = np.arange(9, dtype=np.int32)
    s, _, c, _ = kv.alloc_slot(pa, 4)
    assert c == 0
    kv.cancel_slot(s)                           # prefill never ran
    s2, _, c2, cow2 = kv.alloc_slot(pa, 4)
    assert c2 == 0 and cow2 is None             # no garbage match
    kv.free_slot(s2)                            # normal retire: nodes stay
    _, _, c3, _ = kv.alloc_slot(pa, 4)
    assert c3 == 8                              # both full blocks hit


def test_kv_block_pool_optimistic_admission_and_exhaustion():
    """Admission gates on *prompt* blocks only (growth preempts instead of
    reserving worst case); a pool with every block referenced raises
    PoolExhausted — the engine's preemption trigger — on direct misuse."""
    from repro.launch.serve import KVBlockPool, PoolExhausted
    kv = KVBlockPool(FakeBackend(), max_slots=2, block_size=4, num_blocks=2)
    assert kv.can_admit(4, 60)                  # 1 prompt block fits
    kv.alloc_slot(4, 60)                        # takes the single real block
    assert not kv.can_admit(4, 0)               # no block left for a prompt
    with pytest.raises(PoolExhausted, match="exhausted"):
        kv.alloc_slot(4)                        # direct misuse still raises


def test_tight_block_pool_preempts_instead_of_crashing():
    """An under-provisioned pool (fewer blocks than the aggregate demand)
    must complete every request by preempting victims when decode growth
    exhausts the free list — not crash mid-flight, and not shed work."""
    h = _make_handler(max_batch=4, max_secondaries=0,
                      num_blocks=5, block_size=4,   # 4 real blocks
                      executor=lambda c, f, a: (f(*a), 0.1))
    # prompt 4 + 9 new tokens = 13 -> 4 blocks each vs 4 in the pool
    reqs = [ServeRequest(i, np.zeros(4, np.int32), 9, arrival_t=0.0)
            for i in range(6)]
    rep = h.run(reqs)
    assert len(rep.completions) == 6
    assert sorted(c.rid for c in rep.completions) == list(range(6))
    assert all(len(c.tokens) == 9 for c in rep.completions)
    assert rep.preemptions > 0                  # the pool really squeezed


def test_tight_pool_mid_flight_joins_respect_allocations():
    """Regression: two late arrivals offered to the same in-flight engine
    in one round must be admission-checked against each other's block
    allocations (fits() re-runs after every on_assign), not both against
    stale pre-round pool state."""
    h = _make_handler(max_batch=3, max_secondaries=0,
                      num_blocks=9, block_size=4,   # 8 real blocks
                      executor=lambda c, f, a: (f(*a), 0.5))
    # each request needs 4 blocks (prompt 4 + 12 new = 16 tokens)
    reqs = [ServeRequest(0, np.zeros(4, np.int32), 12, arrival_t=0.0),
            ServeRequest(1, np.zeros(4, np.int32), 12, arrival_t=1.2),
            ServeRequest(2, np.zeros(4, np.int32), 12, arrival_t=1.2)]
    rep = h.run(reqs)                           # crashed before the fix
    assert len(rep.completions) == 3
    assert all(len(c.tokens) == 12 for c in rep.completions)


def test_poisson_arrivals_deterministic():
    a = poisson_arrivals(4.0, 10, seed=3)
    b = poisson_arrivals(4.0, 10, seed=3)
    assert [r.arrival_t for r in a] == [r.arrival_t for r in b]
    assert all(x.arrival_t < y.arrival_t for x, y in zip(a, a[1:]))


# --------------------------------------------------------------------------- #
# client handler: elasticity + continuous batching (fake backend => pure
# virtual-clock scheduling, no model in the loop)
# --------------------------------------------------------------------------- #
class FakeBackend:
    """Token i+1 follows token i; venue time injected via executor.

    Implements both the contiguous cohort protocol (prefill/decode/
    cache_take) and the paged slot protocol (init_paged_pool/paged_fns),
    so handler tests exercise the real KVBlockPool/SlotLedger machinery
    with no model in the loop.
    """

    capacity = 64
    params = None

    def prefill(self, params, toks):
        b = int(toks.shape[0])
        return np.zeros(b, np.int32), {"state": np.zeros((b, 1), np.int32)}

    def decode(self, params, cache, tok, pos):
        return np.asarray(tok)[:, 0] + 1, cache

    def cache_take(self, cache, keep):
        return {"state": cache["state"][np.asarray(keep, np.int32)]}

    # --- paged slot protocol -------------------------------------------
    def init_paged_pool(self, max_slots, num_blocks, block_size):
        return {}

    def paged_fns(self, block_size, window=1, donate=False):
        def prefill_into(params, toks, pool, blk_ids, slots):
            return np.zeros(int(toks.shape[0]), np.int32), pool

        def decode_slots(params, pool, tok, pos, tables):
            return np.asarray(tok)[:, 0] + 1, pool

        def decode_window(params, pool, tok, pos, steps_left, tables):
            # mirrors model.decode_loop: live rows count up, dead rows
            # freeze their token
            cur = np.asarray(tok)[:, 0].astype(np.int32)
            sl = np.asarray(steps_left)
            out = np.zeros((cur.size, window), np.int32)
            for t in range(window):
                cur = np.where(t < sl, cur + 1, cur)
                out[:, t] = cur
            return out, pool

        return prefill_into, decode_slots, decode_window

    def prefill_window_fn(self, block_size, num_steps, donate=False):
        # suffix prefill (prefix hit / restore): first token matches the
        # full-prefill convention (always 0), KV content is not modeled
        def prefill_window(params, pool, toks, pos0, n_tok, tables):
            return np.zeros(int(np.asarray(toks).shape[0]), np.int32), pool

        return prefill_window

    def copy_fn(self, donate=False):
        return lambda pool, src, dst: pool

    def migrate_fn(self):
        # cross-pool KV copy: content is not modeled, the host-side
        # token carry (out[-1]) is what keeps decode deterministic
        return lambda dst, src, sids, dids, sslots, dslots: dst


def _make_handler(**kw):
    from repro.launch.serve import ClientHandler
    ex = kw.pop("executor", lambda clone, fn, args: (fn(*args), 0.05))
    return ClientHandler(FakeBackend(), executor=ex, prompt_pad=4, **kw)


def test_autoscaler_grows_and_ttl_pauses_under_burst():
    h = _make_handler(max_batch=1, max_secondaries=4, use_primary=False)
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=4,
                         arrival_t=0.001 * i) for i in range(12)]
    report = h.run(reqs, drain_idle_s=40.0)     # > PAUSE_IDLE_TTL
    assert len(report.completions) == 12
    assert report.peak_secondaries >= 2         # burst grew the pool
    assert report.pool_stats["resumes"] >= 2    # paused pool resumed, not
    assert report.pool_stats["boots"] == 0      # booted (pre-provisioned)
    # after the idle drain every secondary is paused again
    assert len(h.pool.running_secondaries()) == 0
    assert report.pool_stats["pauses"] >= 2
    # elasticity visible in the samples: grew then shrank
    counts = [n for _, n in report.clone_samples]
    assert max(counts) >= 2 and counts[-1] == 0


def test_handler_overlaps_cohorts_across_clones():
    """2 cohorts on 2 clones must overlap: makespan ~ max, not sum."""
    h = _make_handler(max_batch=1, max_secondaries=2, use_primary=False,
                      executor=lambda c, f, a: (f(*a), 1.0))
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=3,
                         arrival_t=0.0) for i in range(2)]
    report = h.run(reqs)
    # each request: prefill + 3 steps = 4 units of 1.0s (+resume +net);
    # serial would be >= 8s, overlapped is ~4s
    assert report.makespan_s < 6.0
    assert report.p50_latency_s < 6.0


def test_handler_requests_leave_at_step_granularity():
    h = _make_handler(max_batch=2, max_secondaries=1)
    reqs = [ServeRequest(0, np.zeros(4, np.int32), max_new_tokens=2),
            ServeRequest(1, np.zeros(4, np.int32), max_new_tokens=5)]
    report = h.run(reqs)
    by_rid = {c.rid: c for c in report.completions}
    assert by_rid[0].tokens == [0, 1]           # left after 2 tokens
    assert by_rid[1].tokens == [0, 1, 2, 3, 4]  # kept decoding alone
    assert by_rid[0].done_t < by_rid[1].done_t


def test_handler_adopts_supplied_pool_clock():
    """A supplied pool must share the handler's timeline, or TTL reaping
    would run on a clock frozen at 0 and never pause the secondaries."""
    from repro.launch.serve import ClientHandler
    clk = VirtualClock()
    pool = ClonePool(clock=clk)
    h = ClientHandler(FakeBackend(), pool=pool, max_secondaries=2,
                      prompt_pad=4,
                      executor=lambda c, f, a: (f(*a), 0.05))
    assert h.clock is clk
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=2,
                         arrival_t=0.0) for i in range(4)]
    h.run(reqs, drain_idle_s=40.0)
    assert clk.now() > 40.0                     # pool timeline advanced
    assert len(pool.running_secondaries()) == 0  # TTL pause actually fired
    with pytest.raises(TypeError):
        ClientHandler(FakeBackend(), pool=ClonePool(clock=lambda: 0.0))


def test_late_arrival_joins_in_flight_engine_next_step():
    """Paged mode: a request arriving while the only clone is mid-decode is
    admitted into a free slot at the next step boundary — it never waits
    for the cohort to drain, so its TTFT beats step-boundary fusion."""
    def trace():
        return [ServeRequest(0, np.zeros(4, np.int32), max_new_tokens=8,
                             arrival_t=0.0),
                ServeRequest(1, np.zeros(4, np.int32), max_new_tokens=3,
                             arrival_t=1.2)]     # mid-decode of rid 0

    def run(kv):
        h = _make_handler(max_batch=2, max_secondaries=0, kv=kv,
                          executor=lambda c, f, a: (f(*a), 0.5))
        return h.run(trace()), h

    rep_p, h_p = run("paged")
    rep_c, _ = run("contiguous")
    bp = {c.rid: c for c in rep_p.completions}
    bc = {c.rid: c for c in rep_c.completions}
    assert bp[1].ttft_s < bc[1].ttft_s          # joined mid-flight
    assert bp[1].tokens == bc[1].tokens == [0, 1, 2]
    assert bp[0].tokens == bc[0].tokens
    # one engine served both: the join reused the in-flight clone
    assert rep_p.pool_stats["resumes"] == 0
    assert rep_p.kv_mode == "paged" and rep_c.kv_mode == "contiguous"


def test_paged_slots_retire_independently_and_blocks_recycle():
    h = _make_handler(max_batch=3, max_secondaries=0,
                      executor=lambda c, f, a: (f(*a), 0.1))
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=n,
                         arrival_t=0.0) for i, n in enumerate((2, 5, 9))]
    rep = h.run(reqs)
    by = {c.rid: c for c in rep.completions}
    assert [len(by[i].tokens) for i in range(3)] == [2, 5, 9]
    assert by[0].done_t < by[1].done_t < by[2].done_t
    assert 0.0 < rep.kv_util <= 1.0


def test_paged_join_reuses_freed_slot():
    """More requests than slots: late arrivals take slots freed by earlier
    retirements on the same in-flight engine (blocks recycle)."""
    def run(kv):
        h = _make_handler(max_batch=2, max_secondaries=0, kv=kv,
                          executor=lambda c, f, a: (f(*a), 0.5))
        return h.run([
            ServeRequest(0, np.zeros(4, np.int32), 2, arrival_t=0.0),
            ServeRequest(1, np.zeros(4, np.int32), 6, arrival_t=0.0),
            ServeRequest(2, np.zeros(4, np.int32), 2, arrival_t=1.6)])

    rep, rep_c = run("paged"), run("contiguous")
    assert len(rep.completions) == 3
    by = {c.rid: c for c in rep.completions}
    by_c = {c.rid: c for c in rep_c.completions}
    assert by[2].tokens == [0, 1]
    # rid 2 took the slot rid 0 freed on the in-flight engine; under
    # step-boundary fusion it must wait for the whole cohort to drain
    assert by[2].ttft_s < by_c[2].ttft_s
    assert by[2].done_t <= by[1].done_t < by_c[2].done_t


def test_decode_window_token_identical_and_fewer_dispatches():
    """A multi-token decode window must emit exactly the per-token path's
    tokens while issuing ~1/T the decode dispatches — under a fixed
    per-dispatch venue cost that shows up directly as makespan."""
    def run(window):
        calls = {"n": 0}

        def ex(clone, fn, args):
            calls["n"] += 1
            return fn(*args), 0.5               # fixed cost per dispatch
        h = _make_handler(max_batch=2, max_secondaries=0, executor=ex,
                          decode_window=window)
        reqs = [ServeRequest(0, np.zeros(4, np.int32), max_new_tokens=8),
                ServeRequest(1, np.zeros(4, np.int32), max_new_tokens=5)]
        return h.run(reqs), calls["n"]

    rep1, n1 = run(1)
    rep4, n4 = run(4)
    by1 = {c.rid: c.tokens for c in rep1.completions}
    by4 = {c.rid: c.tokens for c in rep4.completions}
    assert by4 == by1                           # token-identical
    assert n4 < n1 / 2                          # window amortizes dispatch
    assert rep4.makespan_s < rep1.makespan_s


def test_decode_window_mid_window_completion_keeps_budgets():
    """Rows hitting their budget mid-window stop at exactly
    ``max_new_tokens`` tokens (the scan parks their writes, the host fold
    truncates at the submitted per-slot count)."""
    h = _make_handler(max_batch=3, max_secondaries=0, decode_window=4,
                      executor=lambda c, f, a: (f(*a), 0.1))
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=n,
                         arrival_t=0.0) for i, n in enumerate((1, 6, 10))]
    rep = h.run(reqs)
    by = {c.rid: c.tokens for c in rep.completions}
    assert [len(by[i]) for i in range(3)] == [1, 6, 10]
    # FakeBackend counts up from the prefill token: budgets sliced exactly
    assert by[2] == list(range(10))


def test_donate_kv_requires_single_run_executor():
    with pytest.raises(ValueError):
        from repro.launch.serve import ClientHandler
        ClientHandler(FakeBackend(), donate_kv=True)


def test_decode_window_rejected_on_contiguous_kv():
    from repro.launch.serve import ClientHandler
    with pytest.raises(ValueError):
        ClientHandler(FakeBackend(), kv="contiguous", decode_window=4,
                      executor=lambda c, f, a: (f(*a), 0.05))


def test_join_prefill_pads_to_power_of_two_buckets():
    """3 simultaneous joins prefill as one bucket-of-4 batched call; the
    prefill sees a padded row whose slot id is out of range."""
    seen = []

    class Probe(FakeBackend):
        def paged_fns(self, block_size, window=1, donate=False):
            pf, ds, dw = FakeBackend.paged_fns(self, block_size, window,
                                               donate)

            def prefill_into(params, toks, pool, blk_ids, slots):
                seen.append((int(toks.shape[0]), np.asarray(slots).copy()))
                return pf(params, toks, pool, blk_ids, slots)

            return prefill_into, ds, dw

    from repro.launch.serve import ClientHandler
    h = ClientHandler(Probe(), prompt_pad=4, max_batch=4, max_secondaries=0,
                      executor=lambda c, f, a: (f(*a), 0.05))
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=2,
                         arrival_t=0.0) for i in range(3)]
    rep = h.run(reqs)
    assert len(rep.completions) == 3
    j, slots = seen[0]
    assert j == 4                               # 3 joins -> bucket of 4
    assert slots[-1] == 4                       # pad row: out-of-range slot
    assert sorted(slots[:3]) == [0, 1, 2]


def test_handler_admission_control_sheds_load():
    h = _make_handler(max_batch=1, queue_depth=4, max_secondaries=1,
                      use_primary=False)
    reqs = [ServeRequest(i, np.zeros(4, np.int32), max_new_tokens=2,
                         arrival_t=0.0) for i in range(10)]
    report = h.run(reqs)
    assert report.rejected > 0
    assert report.accepted + report.rejected == 10
    assert len(report.completions) == report.accepted


# --------------------------------------------------------------------------- #
# heterogeneous fleet: placement engine, fleet autoscaler, escalation,
# chips-aware energy, TTL power-off (ADR-004)
# --------------------------------------------------------------------------- #
def test_placement_engine_cost_vs_urgency():
    """$-policy places bulk on the cheapest adequate tier; urgent demand
    ranks by provisioning latency, so a warm premium clone beats a paused
    cheap one beats a cold boot."""
    import pytest as _pytest
    from repro.core import ClonePool, Policy
    from repro.core.clones import BOOT_SECONDS, CloneState, resume_time
    from repro.core.scheduler import PlacementEngine
    pool = ClonePool(clock=lambda: 0.0)
    pool.provision("x2large", 1, state=CloneState.RUNNING)   # warm premium
    pool.provision("basic", 1)                               # paused cheap
    pe = PlacementEngine(pool, fleet=["basic", "main", "x2large"],
                         policy=Policy.NONE)
    assert pe.choose_type("basic") == "basic"                # cheapest $
    assert pe.choose_type("basic", urgent=True) == "x2large"  # fastest
    assert pe.choose_type("main") == "main"                  # floor holds
    preds = {t: pe.provision_pred(t)
             for t in ("basic", "main", "x2large")}
    assert preds["x2large"].time_s == 0.0
    assert preds["basic"].time_s == _pytest.approx(resume_time(1))
    assert preds["main"].time_s == BOOT_SECONDS
    assert preds["basic"].cost_usd < preds["main"].cost_usd \
        < preds["x2large"].cost_usd
    assert preds["basic"].energy_j < preds["x2large"].energy_j


def test_placement_required_type_escalates_and_degrades_at_top():
    """ISSUE 5 satellite: the KV floor walks ``ClonePool.escalate_type``
    (skipping non-fleet tiers); at the ladder's top (escalate_type ->
    None) the caller degrades gracefully to the biggest fleet tier —
    never an exception."""
    from repro.core import ClonePool
    from repro.core.scheduler import PlacementEngine
    pe = PlacementEngine(ClonePool(clock=lambda: 0.0),
                         fleet=["basic", "main"])
    real = {"basic": 3, "main": 7}
    assert pe.required_type("basic", 2, real.__getitem__) == "basic"
    assert pe.required_type("basic", 5, real.__getitem__) == "main"
    assert pe.required_type("basic", 99, real.__getitem__) == "main"


def test_choose_type_hints_spec_draft_affinity_and_breaker_degrade():
    """ISSUE 10 satellite: ``spec_draft`` picks the cheapest adequate
    tier regardless of policy; ``prefix_affinity`` ranks by cached-prefix
    depth through the full tier ladder while the depth's tier still has a
    serveable RUNNING clone, and degrades to the plain policy ranking
    when that clone's breaker trips — chasing an open-breaker clone's
    blocks would re-prefill on a cold pool anyway."""
    from repro.core import ClonePool, Policy
    from repro.core.clones import CloneState
    from repro.core.scheduler import PlacementEngine
    pool = ClonePool(clock=lambda: 0.0)
    pool.provision("basic", 1, state=CloneState.RUNNING)
    lg = pool.provision("large", 1, state=CloneState.RUNNING)[0]
    pe = PlacementEngine(pool, fleet=["basic", "main", "large", "x2large"],
                         policy=Policy.NONE)
    # spec_draft: cheapest adequate by $-rate; the required floor holds
    assert pe.choose_type("basic", hint="spec_draft") == "basic"
    assert pe.choose_type("main", hint="spec_draft") == "main"
    # prefix_affinity: the deepest live match beats the $-policy pick
    aff = {"large": 32, "basic": 8}
    assert pe.choose_type("basic", hint="prefix_affinity",
                          affinity=aff) == "large"
    # ...through the ladder: a floor above the deepest tier drops it from
    # the candidate set, and the deepest *eligible* live match wins
    assert pe.choose_type("main", hint="prefix_affinity",
                          affinity={"basic": 32, "large": 8}) == "large"
    # a depth only counts while its tier has a RUNNING serveable clone:
    # "x2large" has none, so its depth is dead weight and $-ranking rules
    assert pe.choose_type("basic", hint="prefix_affinity",
                          affinity={"x2large": 64}) == "basic"
    # zero affinity degrades to the plain policy ranking
    assert pe.choose_type("basic", hint="prefix_affinity",
                          affinity={}) == "basic"
    # breaker-open degrade: large's only clone trips, its cached depth
    # must stop counting, and the hint falls back to the $-ranking
    while lg.breaker.state == "closed":
        lg.breaker.record_failure(now=0.0)
    assert not lg.serveable
    assert pe.choose_type("basic", hint="prefix_affinity",
                          affinity=aff) == "basic"


def test_fleet_autoscaler_provisions_per_type_under_budget():
    """Demand buckets land on their placed tiers (resume cheap, boot the
    escalated tier) and the global secondary budget caps the total."""
    from repro.core import ClonePool, Policy
    from repro.core.scheduler import FleetAutoscaler, PlacementEngine
    pool = ClonePool(clock=lambda: 0.0)
    pool.provision("basic", 2)                               # paused
    pe = PlacementEngine(pool, fleet=["basic", "main", "large"],
                         policy=Policy.NONE)
    fa = FleetAutoscaler(pool, pe, base_type="basic", max_secondaries=4)
    targets = fa.step(0.0, [("basic", False, 2), ("large", False, 1)], {})
    assert targets["basic"] == 2 and targets["large"] == 1
    assert len(pool.running_secondaries("basic")) == 2
    assert len(pool.running_secondaries("large")) == 1
    assert pool.stats["resumes"] == 2 and pool.stats["boots"] == 1
    # budget: 10 more bulk units cannot exceed the global cap
    targets = fa.step(1.0, [("basic", False, 10)], {"large": 1})
    assert targets["basic"] + targets.get("large", 0) <= 4
    assert len(pool.running_secondaries()) <= 4


def test_fleet_autoscaler_tier_shift_pauses_stale_type_first():
    """Regression: when demand shifts tiers under a tight cap, the
    surplus pause must hit the *stale* (zero-target) tier — an untyped
    sweep paused the freshly booted target tier and livelocked the
    shift until the idle TTL reaped the stale clones."""
    from repro.core import ClonePool, Policy
    from repro.core.scheduler import FleetAutoscaler, PlacementEngine
    pool = ClonePool(clock=lambda: 0.0)
    pe = PlacementEngine(pool, fleet=["basic", "large"], policy=Policy.NONE)
    fa = FleetAutoscaler(pool, pe, base_type="basic", max_secondaries=2)
    fa.step(0.0, [("basic", False, 2)], {})
    assert len(pool.running_secondaries("basic")) == 2
    fa.step(1.0, [("large", False, 2)], {})
    assert len(pool.running_secondaries("large")) == 2   # target met NOW
    assert len(pool.running_secondaries("basic")) == 0   # stale tier paused


def test_min_secondaries_floor_survives_other_tier_demand():
    """Regression: the base tier's warm floor is reserved before any
    other tier's demand can consume the budget."""
    from repro.core import ClonePool, Policy
    from repro.core.scheduler import FleetAutoscaler, PlacementEngine
    pool = ClonePool(clock=lambda: 0.0)
    pe = PlacementEngine(pool, fleet=["basic", "large"], policy=Policy.NONE)
    fa = FleetAutoscaler(pool, pe, base_type="basic", min_secondaries=2,
                         max_secondaries=4)
    targets = fa.step(0.0, [("large", False, 4)], {})
    assert targets["basic"] == 2          # floor reserved first
    assert targets["large"] == 2          # remaining budget only


def test_free_primary_beats_booting_secondary():
    """Regression: a ready clone (the always-on primary) must never lose
    to one still paying its 32 s boot — readiness dominates tier rank in
    clone selection."""
    h = _make_handler(clone_type="basic", max_batch=1, max_secondaries=1,
                      use_primary=True, provision_paused=False,
                      executor=lambda c, f, a: (f(*a), 0.2))
    rep = h.run([ServeRequest(0, np.zeros(4, np.int32), 3, arrival_t=0.0)])
    assert rep.fleet_mix == {"main": 1}   # served on the idle primary
    assert rep.completions[0].ttft_s < 1.0   # not the secondary's boot


def test_fleet_handler_escalates_kv_hungry_requests():
    """A request whose prompt+window KV demand exceeds the base tier's
    block pool is escalated up the ladder and completes there; bulk stays
    on the cheap tier; the report carries the fleet economics."""
    h = _make_handler(clone_type="basic", fleet=["basic", "main"],
                      max_batch=2, max_secondaries=3, use_primary=False,
                      block_size=8, num_blocks=4,
                      executor=lambda c, f, a: (f(*a), 0.2))
    # rid 0 needs ceil(min(4+40, 64)/8) = 6 blocks > basic's 3 real
    reqs = [ServeRequest(0, np.zeros(4, np.int32), 40, arrival_t=0.0),
            ServeRequest(1, np.zeros(4, np.int32), 4, arrival_t=0.0),
            ServeRequest(2, np.zeros(4, np.int32), 4, arrival_t=0.0)]
    rep = h.run(reqs)
    by = {c.rid: c for c in rep.completions}
    assert sorted(by) == [0, 1, 2]
    assert len(by[0].tokens) == 40
    assert rep.escalations == 1
    assert rep.fleet_mix.get("main", 0) >= 1      # the escalated request
    assert rep.fleet_mix.get("basic", 0) >= 1     # the bulk
    assert rep.cost_usd > 0.0
    assert set(rep.energy_j_by_type) == {"basic", "main"}
    assert rep.clone_seconds_by_type["main"] > 0.0


def test_urgent_priority_lands_on_warm_premium_tier():
    """A high-priority request is placed latency-first: it takes the warm
    premium clone while the bulk behind it waits for the cheap tier's
    resume — and never the other way around."""
    from repro.core.clones import CloneState
    h = _make_handler(clone_type="basic", fleet=["basic", "x2large"],
                      max_batch=1, max_secondaries=2, use_primary=False,
                      executor=lambda c, f, a: (f(*a), 0.2))
    h.pool.provision("x2large", 1, state=CloneState.RUNNING)  # hot spare
    reqs = [ServeRequest(0, np.zeros(4, np.int32), 3, arrival_t=0.0,
                         priority=2, tenant="premium"),
            ServeRequest(1, np.zeros(4, np.int32), 3, arrival_t=0.0,
                         tenant="bulk")]
    rep = h.run(reqs)
    by = {c.rid: c for c in rep.completions}
    assert by[0].venue == "x2large"               # urgent took the spare
    assert by[1].venue == "basic"                 # bulk stayed cheap
    assert rep.fleet_mix == {"x2large": 1, "basic": 1}
    assert by[0].ttft_s < by[1].ttft_s            # no resume on its path
    # demand was tracked per tenant/priority class
    assert ("basic", True, "premium") in h.demand_by_class
    assert ("basic", False, "bulk") in h.demand_by_class


def test_primary_serves_homogeneous_non_main_clone_type():
    """Regression: a homogeneous handler pinned at a non-'main' type with
    no secondaries must still serve on the always-on primary (whose type
    is 'main') — the placement band must not band the standing primary
    out, in either direction of the rank ladder."""
    for ctype in ("basic", "x8large"):
        h = _make_handler(clone_type=ctype, max_batch=2, max_secondaries=0,
                          use_primary=True, provision_paused=False)
        rep = h.run([ServeRequest(0, np.zeros(4, np.int32), 3,
                                  arrival_t=0.0)])
        assert [c.tokens for c in rep.completions] == [[0, 1, 2]]
        assert rep.fleet_mix == {"main": 1}       # served on the primary


def test_contiguous_fleet_respects_placement_band():
    """The contiguous cohort path must seed with the request its clone
    was banded for — a band-blocked FIFO head must neither ride a
    premium clone nor displace the urgent request behind it."""
    from repro.core.clones import CloneState
    h = _make_handler(clone_type="basic", fleet=["basic", "x2large"],
                      kv="contiguous", max_batch=2, max_secondaries=1,
                      use_primary=False,
                      executor=lambda c, f, a: (f(*a), 0.2))
    h.pool.provision("x2large", 1, state=CloneState.RUNNING)
    reqs = [ServeRequest(0, np.zeros(4, np.int32), 3, arrival_t=0.0,
                         tenant="bulk"),
            ServeRequest(1, np.zeros(4, np.int32), 3, arrival_t=0.0,
                         priority=2, tenant="premium")]
    rep = h.run(reqs)
    by = {c.rid: c for c in rep.completions}
    assert by[1].venue == "x2large"               # urgent took the spare
    assert by[0].venue == "basic"                 # bulk waited for cheap


def test_busy_energy_is_chips_aware_x8large_vs_basic():
    """ISSUE 5 satellite: energy bills through TpuEnergyModel with the
    venue's chip count — an x8large step costs exactly
    (8*chip + host)/(1*chip + host) times a basic step, not the same."""
    from repro.core.energy import TpuCoeffs

    def run(ctype):
        h = _make_handler(clone_type=ctype, max_batch=1, max_secondaries=1,
                          use_primary=False,
                          executor=lambda c, f, a: (f(*a), 0.5))
        rep = h.run([ServeRequest(0, np.zeros(4, np.int32), 4,
                                  arrival_t=0.0)])
        return rep

    rep8, rep1 = run("x8large"), run("basic")
    c = TpuCoeffs()
    expect = (8 * c.chip_peak_w + c.host_w) / (1 * c.chip_peak_w + c.host_w)
    assert rep8.busy_energy_j / rep1.busy_energy_j == pytest.approx(expect)
    assert set(rep8.energy_j_by_type) == {"x8large"}
    assert rep8.energy_j_by_type["x8large"] == pytest.approx(
        rep8.busy_energy_j)


def test_drain_powers_off_long_idle_secondaries():
    """ISSUE 5 satellite: the drain loop steps the idle TTLs, so paused
    secondaries idle past OFF_IDLE_TTL actually power off and the report
    surfaces ``power_offs``."""
    from repro.core.clones import OFF_IDLE_TTL, PAUSE_IDLE_TTL, CloneState
    h = _make_handler(max_batch=1, max_secondaries=2, use_primary=False)
    reqs = [ServeRequest(i, np.zeros(4, np.int32), 2, arrival_t=0.0)
            for i in range(4)]
    rep = h.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + OFF_IDLE_TTL + 40.0)
    assert rep.power_offs >= 1
    assert rep.power_offs == rep.pool_stats["offs"]
    assert all(c.state is CloneState.POWERED_OFF
               for c in h.pool.clones if not c.is_primary)


# --------------------------------------------------------------------------- #
# chunked prefill + unified mixed dispatch (ADR-005)
# --------------------------------------------------------------------------- #
def test_pow2_bucket():
    """ISSUE 6 satellite: the one pow2 padding helper every bucketed
    dispatch size goes through (join batches, CoW pair lists, suffix
    windows, chunk counts)."""
    from repro.launch.serve import pow2_bucket
    assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 16]
    assert pow2_bucket(1023) == 1024
    assert pow2_bucket(1024) == 1024
    for bad in (0, -3):
        with pytest.raises(ValueError):
            pow2_bucket(bad)


def test_chunked_prefill_handler_validation():
    """prefill_chunk/mixed_dispatch argument contract: chunking needs a
    backend that supports it (FakeBackend does not -> legacy default),
    mixed dispatch needs chunking."""
    from repro.launch.serve import ClientHandler
    h = _make_handler(max_batch=2)
    assert h.prefill_chunk == 0 and not h.mixed_dispatch
    with pytest.raises(ValueError):
        _make_handler(prefill_chunk=-1)
    with pytest.raises(ValueError):
        _make_handler(prefill_chunk=4)          # FakeBackend: unsupported
    with pytest.raises(ValueError):
        _make_handler(prefill_chunk=0, mixed_dispatch=True)
    with pytest.raises(ValueError):
        ClientHandler(FakeBackend(), kv="contiguous", prompt_pad=4,
                      prefill_chunk=4,
                      executor=lambda c, f, a: (f(*a), 0.05))


def test_mid_flight_join_routes_through_mixed_dispatch():
    """A prefix-hit join landing while the cohort decodes must ride the
    ONE fused mixed dispatch — never a separate suffix-prefill dispatch
    ahead of the decode window."""
    calls = {"mixed": 0, "sfx": 0}

    class ChunkProbe(FakeBackend):
        supports_chunked = True

        def prefill_window_fn(self, block_size, num_steps, donate=False,
                              chunk=0):
            def prefill_window(params, pool, toks, pos0, n_tok, tables):
                calls["sfx"] += 1
                return np.zeros(int(np.asarray(toks).shape[0]),
                                np.int32), pool

            return prefill_window

        def mixed_fn(self, block_size, chunk, num_steps, donate=False):
            def mixed(params, pool, tok, pos, steps_left, tables, stoks,
                      spos, sn, stabs):
                calls["mixed"] += 1
                cur = np.asarray(tok)[:, 0].astype(np.int32)
                sl = np.asarray(steps_left)
                out = np.zeros((cur.size, num_steps), np.int32)
                for t in range(num_steps):
                    cur = np.where(t < sl, cur + 1, cur)
                    out[:, t] = cur
                firsts = np.zeros(int(np.asarray(stoks).shape[0]),
                                  np.int32)
                return out, firsts, pool

            return mixed

    from repro.launch.serve import ClientHandler
    h = ClientHandler(ChunkProbe(), prompt_pad=8, max_batch=4,
                      max_secondaries=0, block_size=4, decode_window=2,
                      prefill_chunk=2, mixed_dispatch=True,
                      executor=lambda c, f, a: (f(*a), 0.05))
    assert h.prefill_chunk == 2 and h.mixed_dispatch
    # rid 0/1 at t=0 form the cohort (distinct prompts — no intra-cohort
    # prefix hit, so both fresh-prefill); rid 2 shares rid 0's first
    # (full) prompt block and lands mid-decode as a prefix-hit suffix
    # join whose divergence sits exactly on the block boundary (no CoW)
    joiner = np.concatenate([np.zeros(4, np.int32), np.ones(4, np.int32)])
    reqs = [ServeRequest(0, np.zeros(8, np.int32), max_new_tokens=6,
                         arrival_t=0.0),
            ServeRequest(1, np.full(8, 2, np.int32), max_new_tokens=6,
                         arrival_t=0.0),
            ServeRequest(2, joiner, max_new_tokens=6, arrival_t=0.06)]
    rep = h.run(reqs)
    assert len(rep.completions) == 3
    assert calls["mixed"] >= 1                  # join fused into the window
    assert calls["sfx"] == 0                    # no serial prefill dispatch


def test_chunked_and_mixed_dispatch_token_identical_end_to_end():
    """Real model, one shared-prefix trace, three serving configs —
    stepwise, chunked (split dispatch), chunked+mixed — must produce
    identical tokens for every request (the ADR-005 bitwise-parity
    claim, end to end through admission/join/fold)."""
    from repro.configs import get_config, reduced_config
    from repro.launch.serve import ClientHandler, LMBackend
    cfg = reduced_config(get_config("smollm-360m"))
    backend = LMBackend(cfg, capacity=32)

    def trace():
        rng = np.random.default_rng(5)
        prefix = rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
        reqs = []
        for i in range(8):
            tail = rng.integers(0, cfg.vocab_size, 4, dtype=np.int32)
            tail[0] = i                        # diverge at block boundary
            reqs.append(ServeRequest(i, np.concatenate([prefix, tail]), 4,
                                     arrival_t=0.08 * i))
        return reqs

    outs = []
    for chunk, mixed in ((0, False), (4, False), (4, True)):
        h = ClientHandler(backend, max_batch=4, prompt_pad=12,
                          block_size=4, max_secondaries=0,
                          decode_window=4, prefill_chunk=chunk,
                          mixed_dispatch=mixed,
                          executor=lambda c, f, a: (f(*a), 0.05))
        rep = h.run(trace())
        assert len(rep.completions) == 8
        outs.append({c.rid: list(map(int, c.tokens))
                     for c in rep.completions})
    assert outs[0] == outs[1] == outs[2]


_CHUNK_LM = []


def _chunk_lm_backend():
    """Shared reduced-model backend for the chunked-serving preemption
    checks (built once; also re-used by test_property.py)."""
    if not _CHUNK_LM:
        from repro.configs import get_config, reduced_config
        from repro.launch.serve import LMBackend
        cfg = reduced_config(get_config("smollm-360m"))
        _CHUNK_LM.append(LMBackend(cfg, capacity=32))
    return _CHUNK_LM[0]


def _run_tight_chunk_trace(seed, chunk, mixed):
    """Serve a seeded shared-prefix trace on a deliberately tight pool
    (preemption + restore pressure) and return the observables that must
    be invariant to prefill chunking: per-request tokens plus the
    refcount-governed pool economics counters."""
    from repro.launch.serve import ClientHandler
    backend = _chunk_lm_backend()
    vocab = backend.cfg.vocab_size
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, 8, dtype=np.int32)
    reqs = []
    for i in range(8):
        tail = rng.integers(0, vocab, 4, dtype=np.int32)
        tail[0] = i                            # diverge at block boundary
        reqs.append(ServeRequest(i, np.concatenate([prefix, tail]), 10,
                                 arrival_t=float(rng.uniform(0.0, 0.4))))
    h = ClientHandler(backend, max_batch=4, prompt_pad=12, block_size=4,
                      num_blocks=9, max_secondaries=0, decode_window=4,
                      prefill_chunk=chunk, mixed_dispatch=mixed,
                      executor=lambda c, f, a: (f(*a), 0.05))
    rep = h.run(reqs)
    return {"tokens": {c.rid: tuple(map(int, c.tokens))
                       for c in rep.completions},
            "served": len(rep.completions),
            "preemptions": rep.preemptions,
            "restored_tokens": rep.restored_tokens,
            "prefix_hits": h.prefix_hit_tokens}


def test_chunked_serving_preemption_restore_token_identical():
    """Mid-stream preemptions under pool pressure: stepwise and
    chunked+mixed serving of the same trace must emit identical tokens
    and identical preemption/restore/prefix-hit economics.  The
    host-side KVBlockPool refcount bookkeeping is shared between the two
    paths, so any divergence here is a chunk-kernel or dispatch-fold
    bug, not an accounting one."""
    for seed in (0, 1):
        a = _run_tight_chunk_trace(seed, 0, False)
        b = _run_tight_chunk_trace(seed, 4, True)
        assert a == b
        assert a["served"] == 8
        assert a["preemptions"] > 0 and a["restored_tokens"] > 0


# --------------------------------------------------------------------------- #
# cross-tier speculative decoding (ADR-008)
# --------------------------------------------------------------------------- #
class SpecFakeBackend(FakeBackend):
    """FakeBackend + the speculative protocol.

    The 'draft' proposes exactly the target's counting continuation
    (tok+1 .. tok+k) and the 'verify' maps every window token v to v+1,
    so acceptance is 1.0 unless the handler's corruption harness flips
    proposals — the host acceptance/EMA/fold machinery runs for real
    with no model in the loop.
    """

    supports_speculative = True
    draft_params = None

    class cfg:                      # corruption path reads vocab_size
        vocab_size = 1 << 30        # the +1 bump never wraps

    def init_draft_pool(self, max_slots, num_blocks, block_size):
        return {}

    def spec_draft_fn(self, block_size, catchup_steps, k_max):
        def draft(dparams, dpool, ctoks, cpos0, n_c, tok, pos, k_live,
                  tables):
            t = np.asarray(tok)[:, 0].astype(np.int32)
            k = np.asarray(k_live).astype(np.int32)
            step = np.arange(1, k_max + 1, dtype=np.int32)
            drafts = np.where(step[None, :] <= k[:, None],
                              t[:, None] + step[None, :], 0)
            return drafts.astype(np.int32), dpool

        return draft

    def spec_verify_fn(self, block_size):
        def verify(params, pool, toks, pos0, n_live, tables):
            return np.asarray(toks).astype(np.int32) + 1, pool

        return verify


def _spec_trace(n=6, new_tokens=9):
    return [ServeRequest(i, np.zeros(4, np.int32), new_tokens,
                         arrival_t=0.15 * i) for i in range(n)]


def _run_spec_handler(speculative, **kw):
    from repro.launch.serve import ClientHandler
    h = ClientHandler(SpecFakeBackend(), prompt_pad=4, max_batch=4,
                      max_secondaries=2, speculative=speculative,
                      executor=kw.pop("executor",
                                      lambda c, f, a: (f(*a), 0.05)),
                      **kw)
    rep = h.run(_spec_trace())
    return rep, h


def test_speculative_validation_errors():
    from repro.launch.serve import ClientHandler
    with pytest.raises(ValueError, match="draft model"):
        _make_handler(speculative=True)     # FakeBackend: no spec support
    kw = dict(prompt_pad=4, executor=lambda c, f, a: (f(*a), 0.05))
    with pytest.raises(ValueError, match="spec_k"):
        ClientHandler(SpecFakeBackend(), speculative=True, spec_k=0, **kw)
    with pytest.raises(ValueError, match="paged"):
        ClientHandler(SpecFakeBackend(), speculative=True,
                      kv="contiguous", **kw)
    with pytest.raises(ValueError, match="mutually exclusive"):
        ClientHandler(SpecFakeBackend(), speculative=True,
                      mixed_dispatch=True, **kw)


def test_speculative_serving_token_identical_and_fewer_dispatches():
    """Oracle draft: the speculative run must emit bitwise the plain
    run's streams, accept every proposal, and spend strictly fewer
    target dispatches per token than stepwise decode."""
    plain, _ = _run_spec_handler(False, decode_window=1)
    spec, h = _run_spec_handler(True, spec_k=4)
    a = {c.rid: list(map(int, c.tokens)) for c in plain.completions}
    b = {c.rid: list(map(int, c.tokens)) for c in spec.completions}
    assert a == b and len(a) == 6
    assert spec.spec_rounds > 0 and spec.spec_tokens > 0
    assert spec.acceptance_rate == 1.0
    assert spec.spec_fallbacks == 0
    # dispatch economy: every spec round emits >= 1 token, most emit K+1
    assert spec.spec_tokens / spec.spec_rounds > 1.5
    assert h.spec_draft_cids                # a draft partner really paired


def test_speculative_corruption_partial_acceptance_token_identical():
    """Randomly corrupted proposals cut acceptance below 1.0 but can
    never change the emitted stream (rejected suffixes are garbage KV
    both pools overwrite on the next round)."""
    plain, _ = _run_spec_handler(False, decode_window=1)
    spec, _ = _run_spec_handler(True, spec_k=4, spec_corruption=0.4)
    a = {c.rid: list(map(int, c.tokens)) for c in plain.completions}
    b = {c.rid: list(map(int, c.tokens)) for c in spec.completions}
    assert a == b
    assert 0.0 < spec.acceptance_rate < 1.0


def test_speculative_acceptance_collapse_falls_back_to_plain_decode():
    """Near-total corruption collapses the acceptance EMA; the engine
    must stickily drop speculation (releasing the draft clone) and keep
    serving the exact same streams non-speculatively."""
    plain, _ = _run_spec_handler(False, decode_window=1)
    spec, h = _run_spec_handler(True, spec_k=4, spec_corruption=0.95)
    a = {c.rid: list(map(int, c.tokens)) for c in plain.completions}
    b = {c.rid: list(map(int, c.tokens)) for c in spec.completions}
    assert a == b
    assert spec.spec_fallbacks >= 1
    assert not any(e.spec_on for e in [])   # engines drained at run end


def test_speculative_no_draft_clone_degrades_nonspeculative():
    """A pool with no acquirable draft partner (max_clones=1: the
    primary is all there is) must serve the trace plainly, counted as a
    fallback — pairing failure is never a stall."""
    from repro.launch.serve import ClientHandler
    clk = VirtualClock()
    h = ClientHandler(SpecFakeBackend(), prompt_pad=4, max_batch=4,
                      pool=ClonePool(clock=clk, max_clones=1),
                      max_secondaries=0, speculative=True, spec_k=4,
                      executor=lambda c, f, a: (f(*a), 0.05))
    rep = h.run(_spec_trace())
    assert len(rep.completions) == 6
    assert rep.spec_rounds == 0
    assert rep.spec_fallbacks >= 1


def test_speculative_lm_serving_token_identical():
    """Real reduced model, oracle draft, mid-stream corruption: the
    speculative handler's streams must be bitwise the plain handler's
    (greedy decode is deterministic; ADR-008 losslessness end-to-end)."""
    import dataclasses

    from repro.configs import get_config, reduced_config
    from repro.launch.serve import ClientHandler, LMBackend
    cfg = reduced_config(get_config("smollm-360m"))
    backend = LMBackend(cfg, capacity=32, draft="oracle")
    vocab = cfg.vocab_size
    rng = np.random.default_rng(11)
    reqs = [ServeRequest(i, rng.integers(0, vocab, 6, dtype=np.int32), 8,
                         arrival_t=float(rng.uniform(0.0, 0.3)))
            for i in range(4)]

    def run(speculative, corruption=0.0):
        h = ClientHandler(backend, max_batch=4, prompt_pad=8,
                          block_size=4, max_secondaries=2,
                          decode_window=1, prefill_chunk=0,
                          speculative=speculative, spec_k=3,
                          spec_corruption=corruption,
                          executor=lambda c, f, a: (f(*a), 0.05))
        rep = h.run([dataclasses.replace(r) for r in reqs])
        return {c.rid: list(map(int, c.tokens)) for c in rep.completions}, \
            rep

    base, _ = run(False)
    for corr in (0.0, 0.35):
        toks, rep = run(True, corr)
        assert toks == base and len(toks) == 4
        assert rep.spec_rounds > 0
        assert rep.acceptance_rate > 0.0


# --------------------------------------------------------------------------- #
# disaggregated prefill/decode (ADR-009)
# --------------------------------------------------------------------------- #
class DisaggFakeBackend(FakeBackend):
    """FakeBackend + the chunked/disagg protocol.

    Prefill (local, chunked, or on the partner) always emits first token
    0 and decode counts up, so streams are deterministic regardless of
    which clone ran the prefill — KV content is not modeled, the
    host-side block bookkeeping and token carry are what's under test.
    """

    supports_chunked = True

    def prefill_window_fn(self, block_size, num_steps, donate=False,
                          chunk=0):
        def prefill_window(params, pool, toks, pos0, n_tok, tables):
            return np.zeros(int(np.asarray(toks).shape[0]), np.int32), pool

        return prefill_window

    def mixed_fn(self, block_size, chunk, steps, donate=False):
        def mixed(params, pool, tok, pos, steps_left, tables,
                  stoks, spos, sn, stabs):
            cur = np.asarray(tok)[:, 0].astype(np.int32)
            sl = np.asarray(steps_left)
            window = max(int(np.max(sl)) if sl.size else 1, 1)
            out = np.zeros((cur.size, window), np.int32)
            for t in range(window):
                cur = np.where(t < sl, cur + 1, cur)
                out[:, t] = cur
            firsts = np.zeros(int(np.asarray(stoks).shape[0]), np.int32)
            return out, firsts, pool

        return mixed

    def migrate_fn(self, compress=False):
        return lambda dst, src, sids, dids, sslots, dslots: dst


def test_disagg_validation_errors():
    from repro.launch.serve import ClientHandler
    ex = lambda c, f, a: (f(*a), 0.05)
    with pytest.raises(ValueError, match="kv='paged'"):
        ClientHandler(DisaggFakeBackend(), executor=ex, prompt_pad=4,
                      kv="contiguous", disagg=True)
    with pytest.raises(ValueError, match="chunked"):
        ClientHandler(FakeBackend(), executor=ex, prompt_pad=4,
                      disagg=True)     # no supports_chunked on the stub
    with pytest.raises(ValueError, match="disagg_link"):
        ClientHandler(DisaggFakeBackend(), executor=ex, prompt_pad=4,
                      disagg=True, disagg_link="carrier-pigeon")
    with pytest.raises(ValueError, match="routing"):
        ClientHandler(DisaggFakeBackend(), executor=ex, prompt_pad=4,
                      routing="bogus")
    with pytest.raises(ValueError, match="kv='paged'"):
        ClientHandler(DisaggFakeBackend(), executor=ex, prompt_pad=4,
                      kv="contiguous", routing="affinity")


def _disagg_trace():
    return [ServeRequest(i, np.full(8, i + 1, np.int32), 4,
                         arrival_t=0.05 * i) for i in range(6)]


def _run_disagg_fake(**kw):
    from repro.launch.serve import ClientHandler
    h = ClientHandler(DisaggFakeBackend(),
                      executor=kw.pop("executor",
                                      lambda c, f, a: (f(*a), 0.05)),
                      prompt_pad=8, max_batch=2, max_secondaries=4,
                      block_size=4, prefill_chunk=4, use_primary=False,
                      fleet=["basic", "large"], clone_type="basic", **kw)
    rep = h.run(_disagg_trace())
    return h, rep, {c.rid: list(map(int, c.tokens))
                    for c in rep.completions}


def test_disagg_handoffs_colocated_split_and_transfer_accounting():
    """Cold prompts over the disagg_min_prompt threshold hand off (one
    count + wire bytes/seconds each), a threshold above the effective
    prompt keeps every candidate co-located (planner says no, zero wire
    cost), and the int8 handoff ships <= half the uncompressed bytes —
    with every stream identical to the non-disagg baseline (the stub
    decodes the same count-up sequence wherever the prefill ran)."""
    _, rep0, t0 = _run_disagg_fake()
    assert len(t0) == 6 and rep0.disagg_handoffs == 0
    assert rep0.kv_transfer_bytes == 0 and rep0.kv_transfer_s == 0.0
    _, rep1, t1 = _run_disagg_fake(disagg=True, disagg_min_prompt=6,
                                   disagg_prefill_type="large")
    assert t1 == t0
    assert rep1.disagg_handoffs == 6       # every eff-8 prompt ships
    assert rep1.disagg_colocated == 0
    assert rep1.disagg_fallbacks == 0
    assert rep1.kv_transfer_bytes > 0 and rep1.kv_transfer_s > 0.0
    _, rep2, t2 = _run_disagg_fake(disagg=True, disagg_min_prompt=6,
                                   disagg_prefill_type="large",
                                   disagg_compress=True)
    assert t2 == t0
    assert rep2.disagg_handoffs == 6
    assert 0 < rep2.kv_transfer_bytes < 0.5 * rep1.kv_transfer_bytes
    # threshold above the padded prompt: the planner keeps every
    # candidate local — co-located counts, nothing on the wire
    _, rep3, t3 = _run_disagg_fake(disagg=True, disagg_min_prompt=100,
                                   disagg_prefill_type="large")
    assert t3 == t0
    assert rep3.disagg_handoffs == 0 and rep3.disagg_colocated == 6
    assert rep3.kv_transfer_bytes == 0


def test_disagg_partner_death_degrades_to_colocated_prefill():
    """Killing the shared prefill partner mid-trace must degrade every
    attached engine to co-located prefill (counted as fallbacks) with
    zero token loss — a partner death is never a stall and never
    corrupts a stream."""
    from repro.core.faults import CloneFault
    from repro.launch.serve import ClientHandler
    ex = lambda c, f, a: (f(*a), 0.05)

    def run(faults):
        # decode on the primary: the shared large partner is then the
        # only running secondary, so cid=None targets it at fire time
        h = ClientHandler(DisaggFakeBackend(), executor=ex, prompt_pad=4,
                          max_batch=8, max_secondaries=2, block_size=4,
                          prefill_chunk=4, fleet=["main", "large"],
                          disagg=True, disagg_min_prompt=1,
                          disagg_prefill_type="large", faults=faults)
        rep = h.run([ServeRequest(i, np.full(8, i + 1, np.int32), 4,
                                  arrival_t=0.3 * i) for i in range(4)])
        return rep, {c.rid: list(map(int, c.tokens))
                     for c in rep.completions}

    rep0, t0 = run(None)
    assert rep0.disagg_handoffs == 4 and rep0.disagg_fallbacks == 0
    rep1, t1 = run([CloneFault(at=0.35, kind="kill")])
    assert t1 == t0                        # count-up streams, no loss
    assert rep1.disagg_fallbacks >= 1
    assert rep1.disagg_handoffs < 4        # post-death prompts stay local
    assert rep1.faults_injected == 1


def test_disagg_lm_serving_token_identical():
    """Real reduced model: disaggregated prefill (partner clone + paged
    block migration) must be bitwise the co-located handler on the same
    trace when uncompressed, and the int8 handoff must complete every
    stream at <= half the wire bytes (ADR-009 end to end)."""
    from repro.launch.serve import ClientHandler
    backend = _chunk_lm_backend()
    vocab = backend.cfg.vocab_size
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, vocab, 8, dtype=np.int32)
    reqs = []
    for i in range(4):
        tail = rng.integers(0, vocab, 4, dtype=np.int32)
        tail[0] = i
        reqs.append(ServeRequest(i, np.concatenate([prefix, tail]), 6,
                                 arrival_t=0.05 * i))

    def run(**kw):
        h = ClientHandler(backend, max_batch=4, prompt_pad=12,
                          block_size=4, max_secondaries=4,
                          decode_window=4,
                          executor=lambda c, f, a: (f(*a), 0.05), **kw)
        rep = h.run([dataclasses.replace(r) for r in reqs])
        return rep, {c.rid: list(map(int, c.tokens))
                     for c in rep.completions}

    import dataclasses
    _, t0 = run()
    rep1, t1 = run(fleet=["basic", "large"], clone_type="basic",
                   disagg=True, disagg_min_prompt=1,
                   disagg_prefill_type="large")
    assert rep1.disagg_handoffs >= 1
    assert t1 == t0 and len(t1) == 4
    rep2, t2 = run(fleet=["basic", "large"], clone_type="basic",
                   disagg=True, disagg_min_prompt=1,
                   disagg_prefill_type="large", disagg_compress=True)
    assert len(t2) == 4
    assert all(len(v) == 6 for v in t2.values())
    assert 0 < rep2.kv_transfer_bytes < 0.5 * rep1.kv_transfer_bytes


def _assert_blocks_conserved(kv):
    """Post-drain allocator conservation for one KVBlockPool: no live
    refs, no leaked block, no double-free (free / cached-free partition
    the physical blocks; the trash block stays clean)."""
    assert not np.asarray(kv.ref).any(), "live refcount after drain"
    free = set(kv._free_blocks)
    cached = set(kv._cached_free)
    assert len(kv._free_blocks) == len(free), "double-free: dup free list"
    assert not free & cached
    assert free | cached == set(range(1, kv.num_blocks)), "leaked block"
    assert 0 not in free and 0 not in cached


def run_disagg_affinity_trace(seed, *, routing="ledger", disagg=False,
                              compress=False):
    """Serve a seeded shared-prefix trace (2 families x 3 requests) on
    the reduced model and return its observables; asserts KV-block
    conservation over every per-clone pool and partner scratch pool on
    the way out.  The ADR-009 property harness: ``test_property.py``
    sweeps (seed, routing, disagg, compress) through this."""
    from repro.launch.serve import ClientHandler
    backend = _chunk_lm_backend()
    vocab = backend.cfg.vocab_size
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, 8, dtype=np.int32)
                for _ in range(2)]
    reqs = []
    for i in range(6):
        tail = rng.integers(0, vocab, 4, dtype=np.int32)
        tail[0] = i                        # diverge at block boundary
        reqs.append(ServeRequest(
            i, np.concatenate([prefixes[i % 2], tail]), 6,
            arrival_t=float(rng.uniform(0.0, 0.6))))
    kw = {}
    if disagg:
        kw = dict(fleet=["basic", "large"], disagg=True,
                  disagg_min_prompt=1, disagg_prefill_type="large",
                  disagg_compress=compress)
    h = ClientHandler(backend, max_batch=2, prompt_pad=12, block_size=4,
                      max_secondaries=4, decode_window=4,
                      clone_type="basic", use_primary=False,
                      routing=routing,
                      executor=lambda c, f, a: (f(*a), 0.05), **kw)
    rep = h.run(reqs)
    for kv in list(h._kv_pools.values()) + list(h._prefill_pools.values()):
        _assert_blocks_conserved(kv)
    return {"tokens": {c.rid: tuple(map(int, c.tokens))
                       for c in rep.completions},
            "served": len(rep.completions),
            "offered": 6,
            "handoffs": rep.disagg_handoffs,
            "fallbacks": rep.disagg_fallbacks,
            "xfer_bytes": rep.kv_transfer_bytes}


def test_disagg_affinity_routing_conserves_blocks_and_tokens():
    """Deterministic twin of the ADR-009 property (test_property.py):
    any routing mode x disagg handoff serves the whole shared-prefix
    trace with zero block leak and — compression off — streams bitwise
    identical to the co-located ledger-routed baseline."""
    base = run_disagg_affinity_trace(3)
    assert base["served"] == 6 and base["handoffs"] == 0
    for routing in ("affinity", "random"):
        out = run_disagg_affinity_trace(3, routing=routing, disagg=True)
        assert out["tokens"] == base["tokens"]
        assert out["handoffs"] >= 1 and out["fallbacks"] == 0
    comp = run_disagg_affinity_trace(3, routing="affinity", disagg=True,
                                     compress=True)
    assert comp["served"] == 6
    assert 0 < comp["xfer_bytes"] < out["xfer_bytes"]


@pytest.mark.parametrize("acquire", ["_acquire_prefill_clone",
                                     "_acquire_draft_clone"])
def test_partner_acquire_swallows_only_pool_exhaustion(acquire, monkeypatch):
    """A partner clone the pool cannot provide degrades the engine (None);
    any other error out of the pool, such as a device or compile failure,
    propagates instead of being swallowed."""
    from repro.core.clones import ClonePoolExhausted
    from repro.launch.serve import ClientHandler
    h = ClientHandler(FakeBackend(), executor=lambda c, f, a: (f(*a), 0.01))

    def exhausted(*a, **k):
        raise ClonePoolExhausted("clone pool exhausted")

    def broken(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(h.pool, "acquire", exhausted)
    assert getattr(h, acquire)(None) is None
    monkeypatch.setattr(h.pool, "acquire", broken)
    with pytest.raises(RuntimeError, match="device lost"):
        getattr(h, acquire)(None)
