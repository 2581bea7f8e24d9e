"""Serving stack: ThinkAir's Client Handler for LM inference.

Two layers share one model binding (``LMBackend``):

``ServingEngine`` — the batch-at-a-time path (seed behaviour).  Each request
batch is a remoteable method invocation: the ExecutionController decides
placement (local small venue vs cloud clones) per batch from profiled
history; long-context requests whose KV-cache working set exceeds the
default clone's memory are escalated to a bigger clone type (the paper's
OutOfMemoryError path); prefill for large batches can be split across k
clones (the paper's parallelization path).

``ClientHandler`` — the event-driven continuous-batching server (paper
§5.2-§5.3, the tentpole of the Client Handler refactor).  Requests arrive
on a shared :class:`~repro.core.clock.VirtualClock`, pass admission control
(:class:`~repro.core.scheduler.AdmissionQueue`), and are formed into
*cohorts* of up to ``max_batch`` requests.  Each cohort's prefill and every
decode step is a non-blocking :class:`~repro.core.dispatch.Dispatcher` task
on one clone, so cohorts on different clones genuinely overlap on the
timeline.  Requests **leave** their cohort at decode-step granularity the
moment they hit their token budget (the cohort's KV cache shrinks in
place), and new arrivals **enter** service at the next step boundary on any
free clone — they never wait for a whole batch to drain.  A
:class:`~repro.core.scheduler.FleetAutoscaler` provisions and TTL-pauses
secondaries through the ClonePool lifecycle, which makes the paper's
elasticity claim measurable as p50/p99 latency and tokens/s under Poisson
offered load (see ``benchmarks/serving_load.py``).

The fleet is **heterogeneous** (ADR-004): ``ClientHandler(fleet=[...])``
serves across several paper-Table-1 clone types at once.  Demand is
bucketed per tenant/priority class and per KV footprint; a
:class:`~repro.core.scheduler.PlacementEngine` places each bucket on a
tier by cost/energy/latency (``placement_policy``), and a request whose
prompt+window KV demand exceeds its tier's block pool is *escalated* up
the :meth:`~repro.core.clones.ClonePool.escalate_type` ladder — the
serving-layer analogue of the paper's OutOfMemoryError -> bigger-VM flow
(§5.4).  Per-type block pools scale with the tier's memory ladder, busy
energy is billed chips-aware through
:meth:`~repro.core.energy.TpuEnergyModel.busy_j`, and the
:class:`ServeReport` carries the fleet economics (per-type clone-seconds,
$-cost, per-type energy, the served fleet mix).

KV cache modes: the default ``kv="paged"`` path batches at *slot*
granularity — each clone runs a :class:`_SlotEngine` whose requests each
own a per-slot decode cursor and a row of a block table over a fixed
:class:`KVBlockPool`; a late arrival is prefilled into any free slot of an
in-flight engine at the next decode step (no step-boundary fusion, no
``cache_take`` re-gather on retire).  ``kv="contiguous"`` keeps the PR-1
cohort path — one shared cursor, fusion only at the same step boundary —
as the measurable baseline (see ``benchmarks/serving_load.py`` and
``docs/architecture.md``).  Weights are resident on the clones (serving
fleet), so per-request network cost is prompt/token traffic only — unlike
the offload path, which ships the method's whole state.

The paged pool is a **refcounted copy-on-write prefix cache** with
**preemption-aware slot scheduling** (ADR-003): prompt blocks are
content-indexed so shared prefixes map into new slots at refcount + 1
instead of re-prefilling (the first divergent block is copied-on-write on
device), admission reserves only prompt blocks, and a pool exhausting
mid-decode evicts a victim slot for prefix-accelerated restore instead of
raising — overload degrades into latency, not failure (paper §5's
many-users elasticity claim at the KV level).  ``prefix_cache=False``
keeps the unshared path as the measurable baseline.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core import (ClonePool, ExecutionController, Policy,
                        RemoteableMethod, TpuEnergyModel)
from repro.core.clock import VirtualClock
from repro.core.clones import (CLONE_TYPES, KV_SCALE_BY_CLONE_TYPE,
                               PAUSE_IDLE_TTL, CircuitBreaker,
                               ClonePoolExhausted)
from repro.core.dispatch import Dispatcher
from repro.core.faults import CloneFault, FaultInjector
from repro.core.gateway import StreamingGateway
from repro.core.scheduler import (AdmissionQueue, FleetAutoscaler,
                                  PlacementEngine, ServeCompletion,
                                  ServeRequest, SlotLedger, poisson_arrivals)
from repro.core.tracing import span
from repro.core.venues import (LINKS, Venue, kv_block_bytes, pytree_bytes,
                               transfer_time)
from repro.kernels import ops as kops
from repro.launch import steps as S
from repro.launch.compile_cache import use_compile_cache
from repro.models import model


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (n >= 1): the dispatch bucket size.

    Every variable-size batched dispatch (join prefill rows, CoW pairs,
    suffix rows and steps, prefill chunk steps) pads to one of these so
    each jitted graph only ever compiles O(log) shape variants.
    """
    if n < 1:
        raise ValueError(f"bucket size needs n >= 1: {n}")
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 16


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: List[int]
    prefill_venue: str
    decode_venue: str
    latency_s: float
    escalations: int


class LMBackend:
    """Model binding: params + jitted prefill/decode + cache batch surgery.

    ``draft`` arms cross-tier speculative decoding (ADR-008): a
    :class:`~repro.configs.ModelConfig` binds a reduced-cost draft model
    sharing the target's vocab (its own params, context, and paged pool);
    the string ``"oracle"`` aliases the target itself as its own draft —
    the acceptance-rate-1.0 harness benchmarks and tests corrupt
    deterministically.  ``None`` (default) leaves speculation off.

    Weights are random from ``seed``.  Attention runs as
    :func:`repro.kernels.ops.serving_impl` decides for the platform:
    Mosaic kernels on the TPU, the XLA path on the CPU.
    """

    def __init__(self, cfg, capacity: int = 256, draft=None, seed: int = 0):
        self.cfg = cfg
        self.capacity = capacity
        impl = kops.serving_impl()
        self.ctx = S.make_context(None, impl=impl,
                                  moe_capacity_factor=(
                                      cfg.n_experts / cfg.top_k
                                      if cfg.is_moe else 1.25))
        self.params = model.init(cfg, jax.random.PRNGKey(seed))
        self.draft_cfg = None
        self.draft_params = None
        self.draft_ctx = None
        if draft == "oracle":
            self.draft_cfg, self.draft_ctx = cfg, self.ctx
            self.draft_params = self.params
        elif draft is not None:
            if draft.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft model must share the target's vocab "
                    f"({draft.vocab_size} != {cfg.vocab_size}): acceptance "
                    "compares token ids directly")
            self.draft_cfg = draft
            self.draft_ctx = S.make_context(None, impl=impl,
                                            moe_capacity_factor=(
                                                draft.n_experts / draft.top_k
                                                if draft.is_moe else 1.25))
            self.draft_params = model.init(draft, jax.random.PRNGKey(7))
        cap = capacity

        def prefill_fn(params, tokens):
            logits, cache = model.forward(cfg, params, {"tokens": tokens},
                                          self.ctx, "prefill",
                                          cache_capacity=cap)
            return jnp.argmax(logits, -1), cache

        def decode_fn(params, cache, tokens, pos):
            logits, cache = model.decode_step(cfg, params, cache, tokens,
                                              pos, self.ctx)
            return jnp.argmax(logits, -1), cache

        self.prefill = jax.jit(prefill_fn)
        self.decode = jax.jit(decode_fn)
        # locate each cache leaf's batch/capacity axes by diffing shapes
        self._batch_axis, self._cap_axis = model.cache_axes(cfg)
        self._block_axis = model.paged_block_axes(cfg)
        self._paged_fns: Dict[tuple, tuple] = {}      # (bs, donate)
        self._paged_win_fns: Dict[tuple, object] = {}  # (bs, window, donate)
        self._paged_sfx_fns: Dict[tuple, object] = {}  # (bs, T, C, donate)
        self._paged_mix_fns: Dict[tuple, object] = {}  # (bs, C, T, donate)
        self._copy_fns: Dict[bool, object] = {}        # donate -> fn
        self._spec_fns: Dict[tuple, tuple] = {}        # (bs, Tc, K)

    @property
    def supports_chunked(self) -> bool:
        """Whether chunked prefill / mixed dispatch cover this config."""
        return model.supports_chunked_prefill(self.cfg)

    @property
    def supports_speculative(self) -> bool:
        """Whether a draft model is bound (and the target can run the
        chunked verify pass — same layer requirement as ADR-005)."""
        return self.draft_cfg is not None and self.supports_chunked

    @property
    def draft_cost_ratio(self) -> float:
        """Draft/target parameter-count ratio — the *informational*
        per-step cost ratio.  At smoke scale this is embedding-dominated
        (vocab 256), so benchmarks model venue time with an explicit
        ``--draft-cost`` instead (docs/benchmarks.md)."""
        if self.draft_params is None:
            return 1.0
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(self.params))
        d = sum(int(np.prod(x.shape))
                for x in jax.tree.leaves(self.draft_params))
        return d / max(n, 1)

    def cache_mem_bytes(self, batch: int) -> int:
        return pytree_bytes(model.abstract_cache(self.cfg, batch,
                                                 self.capacity))

    def cache_take(self, cache, keep_idx) -> Dict:
        """Shrink a cohort cache to the surviving batch rows."""
        idx = jnp.asarray(np.asarray(keep_idx, np.int32))

        def take(leaf, ax):
            return leaf if ax is None else jnp.take(leaf, idx, axis=ax)

        return jax.tree.map(take, cache, self._batch_axis)

    # ---------------------------------------------------------------- paged
    def init_paged_pool(self, max_slots: int, num_blocks: int,
                        block_size: int):
        """Zero KV block pool + per-slot state rows (block 0 = trash)."""
        return model.init_paged_cache(self.cfg, max_slots, num_blocks,
                                      block_size)

    def init_draft_pool(self, max_slots: int, num_blocks: int,
                        block_size: int):
        """Zero paged pool for the *draft* model, same block geometry as
        the target pool so the two share one set of block tables
        (ADR-008: no extra host bookkeeping for the draft side)."""
        return model.init_paged_cache(self.draft_cfg, max_slots, num_blocks,
                                      block_size)

    def spec_draft_fn(self, block_size: int, catchup_steps: int,
                      k_max: int):
        """Jitted draft half of a speculative round (ADR-008).

        ``fn(dparams, dpool, ctoks (S,Tc), cpos0 (S,), n_c (S,), tok
        (S,1), pos (S,), k_live (S,), tables (S,M))`` runs the draft
        model's catch-up (teacher-forcing the ``n_c`` committed target
        tokens it has not yet ingested, from each row's draft cursor
        ``cpos0``) plus up to ``k_max`` greedy proposal steps per row in
        ONE dispatch (:func:`model.draft_loop`), returning ``(drafts
        (S, k_max), new_dpool)``.  Cached per (block_size,
        catchup_steps, k_max); callers bucket ``catchup_steps`` to
        powers of two so only O(log) variants compile."""
        key = ("draft", block_size, catchup_steps, k_max)
        fn = self._spec_fns.get(key)
        if fn is not None:
            return fn
        dcfg, dctx, capacity = self.draft_cfg, self.draft_ctx, self.capacity

        def draft(dparams, dpool, ctoks, cpos0, n_c, tok, pos, k_live,
                  tables):
            return model.draft_loop(
                dcfg, dparams, dpool, ctoks, cpos0, n_c, tok, pos, k_live,
                dctx, block_tables=tables, block_size=block_size,
                catchup_steps=catchup_steps, num_steps=k_max,
                capacity=capacity)

        fn = jax.jit(draft)
        self._spec_fns[key] = fn
        return fn

    def spec_verify_fn(self, block_size: int):
        """Jitted verify half of a speculative round (ADR-008).

        ``fn(params, pool, toks (S, K+1), pos0 (S,), n_live (S,), tables
        (S,M))`` scores each row's current token plus its ``n_live - 1``
        draft proposals in ONE chunked teacher-forced pass over the
        *target* (:func:`model.verify_window` through the GQA-fused
        ``paged_verify`` kernel), returning ``(greedy (S, K+1),
        new_pool)`` — the grid the host accepts with
        :func:`model.spec_accept`."""
        key = ("verify", block_size)
        fn = self._spec_fns.get(key)
        if fn is not None:
            return fn
        cfg, ctx, capacity = self.cfg, self.ctx, self.capacity

        def verify(params, pool, toks, pos0, n_live, tables):
            return model.verify_window(
                cfg, params, pool, toks, pos0, n_live, ctx,
                block_tables=tables, block_size=block_size,
                capacity=capacity)

        fn = jax.jit(verify)
        self._spec_fns[key] = fn
        return fn

    def paged_fns(self, block_size: int, window: int = 1,
                  donate: bool = False):
        """(prefill_into, decode_slots, decode_window) jitted fns.

        ``prefill_into(params, toks (J,P), pool, blk_ids (J,nb0), slots
        (J,))`` prefills J prompts in one batched call and scatters each
        row's KV into its pool blocks (and its recurrent state into its
        slot row), returning ``(first_tokens (J,), new_pool)``.  Joins
        landing at the same step boundary therefore cost one prefill, like
        a contiguous cohort.  Rows whose slot id is out of range (the
        power-of-two bucket padding) scatter nowhere: their state-row
        update is dropped and their KV lands in the trash block.

        ``decode_slots(params, pool, tok (S,1), pos (S,), tables (S,M))``
        runs one decode step for every slot at its own cursor, returning
        ``(next_tokens (S,), new_pool)``.  Inactive slots must carry
        ``pos=0`` and an all-zero table row so their writes land in the
        trash block.

        ``decode_window(params, pool, tok (S,1), pos (S,), steps_left (S,),
        tables (S,M))`` is the flash-decoding fast path: ``window`` greedy
        steps fused into one ``lax.scan`` dispatch (``model.decode_loop``),
        returning ``(tokens (S, window), new_pool)``.  Rows exhaust their
        ``steps_left`` mid-window and park further writes in the trash
        block until the host-side boundary.

        ``donate=True`` adds ``donate_argnums`` on the pool so each step
        updates the KV pool in place instead of deep-copying it.  A donated
        call *consumes* its pool argument — callers whose executor re-runs
        a closure (the default simulated Venue re-times cheap calls) must
        keep ``donate=False``; see docs/architecture.md ADR-002.
        """
        # prefill_into / decode_slots don't depend on the window: cache
        # them under (bs, donate) so handlers with different windows share
        # one compiled prefill graph; only decode_window is window-keyed
        base_key = (block_size, donate)
        win_key = (block_size, window, donate)
        if base_key in self._paged_fns and win_key in self._paged_win_fns:
            return self._paged_fns[base_key] + (self._paged_win_fns[win_key],)
        cfg, ctx = self.cfg, self.ctx
        b_ax, c_ax, p_ax = self._batch_axis, self._cap_axis, self._block_axis
        capacity = self.capacity

        def prefill_into(params, toks, pool, blk_ids, slots):
            j, nb0 = blk_ids.shape
            with jax.named_scope("prefill"):
                logits, pcache = model.forward(
                    cfg, params, {"tokens": toks}, ctx, "prefill",
                    cache_capacity=nb0 * block_size)
            flat_ids = blk_ids.reshape(-1)

            def scatter(pool_leaf, pre, bax, cax, pax):
                if cax is None:                      # per-slot state rows
                    lp = jnp.moveaxis(pool_leaf, bax, 0)
                    rows = jnp.moveaxis(pre, bax, 0)
                    return jnp.moveaxis(lp.at[slots].set(rows, mode="drop"),
                                        0, bax)
                # pool (.., N, Hkv, bs, D) and contiguous cache
                # (.., J, cap, Hkv, D) both move to (blocks, tokens, ..)
                lp = jnp.moveaxis(pool_leaf, (bax, pax), (0, 1))
                pr = jnp.moveaxis(pre, (bax, cax), (0, 1))
                pr = pr.reshape((j * nb0, block_size) + pr.shape[2:])
                return jnp.moveaxis(lp.at[flat_ids].set(pr), (0, 1),
                                    (bax, pax))

            with jax.named_scope("kv_scatter"):
                pool = jax.tree.map(scatter, pool, pcache, b_ax, c_ax, p_ax)
            return jnp.argmax(logits, -1), pool

        def decode_slots(params, pool, tok, pos, tables):
            logits, pool = model.decode_step(
                cfg, params, pool, tok, pos, ctx, block_tables=tables,
                block_size=block_size)
            return jnp.argmax(logits, -1), pool

        def decode_window(params, pool, tok, pos, steps_left, tables):
            return model.decode_loop(
                cfg, params, pool, tok, pos, steps_left, ctx,
                block_tables=tables, block_size=block_size,
                num_steps=window, capacity=capacity)

        if base_key not in self._paged_fns:
            self._paged_fns[base_key] = (
                jax.jit(prefill_into, donate_argnums=(2,)),
                jax.jit(decode_slots, donate_argnums=(1,))) if donate else (
                jax.jit(prefill_into), jax.jit(decode_slots))
        self._paged_win_fns[win_key] = jax.jit(
            decode_window, donate_argnums=(1,) if donate else ())
        return self._paged_fns[base_key] + (self._paged_win_fns[win_key],)

    def prefill_window_fn(self, block_size: int, num_steps: int,
                          donate: bool = False, chunk: int = 0):
        """Jitted suffix prefill for prefix-hit / restored rows.

        ``fn(params, pool, toks (J,T), pos0 (J,), n_tok (J,), tables
        (J,M)) -> (first_tokens (J,), new_pool)`` — a teacher-forced
        :func:`model.prefill_loop` scan: row i writes its ``n_tok[i]``
        suffix tokens from position ``pos0[i]`` through its block table
        and returns the greedy token after its last suffix position.
        Rows with ``n_tok == 0`` (bucket padding) park in the trash
        block.

        ``chunk > 0`` switches to the chunked-prefill path
        (:func:`model.prefill_chunks`, ADR-005): the scan advances
        ``chunk`` tokens per step through the paged chunk kernel, so the
        same ``num_steps``-token suffix costs ⌈num_steps/chunk⌉
        sequential steps — token-identical to the stepwise scan.  Cached
        per (block_size, num_steps, chunk, donate), so suffix batches
        bucketed to powers of two compile O(log) variants."""
        key = (block_size, num_steps, chunk, donate)
        fn = self._paged_sfx_fns.get(key)
        if fn is not None:
            return fn
        cfg, ctx, capacity = self.cfg, self.ctx, self.capacity

        if chunk > 0:
            if not self.supports_chunked:
                raise ValueError("chunked prefill requires all-attention "
                                 "windowless layers (see "
                                 "model.supports_chunked_prefill)")
            n_chunks = -(-num_steps // chunk)

            def prefill_window(params, pool, toks, pos0, n_tok, tables):
                return model.prefill_chunks(
                    cfg, params, pool, toks, pos0, n_tok, ctx,
                    block_tables=tables, block_size=block_size,
                    chunk=chunk, num_steps=n_chunks, capacity=capacity)
        else:
            def prefill_window(params, pool, toks, pos0, n_tok, tables):
                return model.prefill_loop(
                    cfg, params, pool, toks, pos0, n_tok, ctx,
                    block_tables=tables, block_size=block_size,
                    num_steps=num_steps, capacity=capacity)

        fn = jax.jit(prefill_window,
                     donate_argnums=(1,) if donate else ())
        self._paged_sfx_fns[key] = fn
        return fn

    def mixed_fn(self, block_size: int, chunk: int, num_steps: int,
                 donate: bool = False):
        """Jitted unified mixed prefill/decode engine step (ADR-005).

        ``fn(params, pool, tok (S,1), pos (S,), steps_left (S,), tables
        (S,M), stoks (J,T), spos (J,), sn (J,), stabs (J,M)) ->
        (tokens (S, num_steps), first_tokens (J,), new_pool)`` — one
        :func:`model.mixed_loop` scan fusing the decode cohort's window
        with the joining rows' chunked suffix prefill, so a join or
        restore never stalls decode behind a separate dispatch.
        ``num_steps`` scan steps cover the longer tile (decode window vs
        ⌈suffix/chunk⌉ chunk steps); the shorter tile runs dead past its
        end.  Cached per (block_size, chunk, num_steps, donate)."""
        key = (block_size, chunk, num_steps, donate)
        fn = self._paged_mix_fns.get(key)
        if fn is not None:
            return fn
        if not self.supports_chunked:
            raise ValueError("mixed dispatch requires all-attention "
                             "windowless layers (see "
                             "model.supports_chunked_prefill)")
        cfg, ctx, capacity = self.cfg, self.ctx, self.capacity

        def mixed(params, pool, tok, pos, steps_left, tables,
                  stoks, spos, sn, stabs):
            return model.mixed_loop(
                cfg, params, pool, tok, pos, steps_left,
                stoks, spos, sn, ctx, block_tables=tables,
                sfx_tables=stabs, block_size=block_size, chunk=chunk,
                num_steps=num_steps, capacity=capacity)

        fn = jax.jit(mixed, donate_argnums=(1,) if donate else ())
        self._paged_mix_fns[key] = fn
        return fn

    def copy_fn(self, donate: bool = False):
        """Jitted copy-on-write: ``fn(pool, src (C,), dst (C,))`` copies
        the listed KV blocks on device across every pool leaf with a
        capacity axis (per-slot state rows pass through untouched) — one
        fused dispatch per CoW batch, see ``ops.copy_blocks``."""
        if self._copy_fns.get(donate) is None:
            b_ax, c_ax = self._batch_axis, self._cap_axis

            def copy_into(pool, src, dst):
                def cp(leaf, bax, cax):
                    if cax is None:
                        return leaf
                    return kops.copy_blocks(leaf, src, dst, axis=bax)

                return jax.tree.map(cp, pool, b_ax, c_ax)

            self._copy_fns[donate] = jax.jit(
                copy_into, donate_argnums=(0,) if donate else ())
        return self._copy_fns[donate]

    def migrate_fn(self, compress: bool = False):
        """Jitted cross-pool KV migration (ADR-006): ``fn(dst_pool,
        src_pool, src_ids (C,), dst_ids (C,), src_slots (J,), dst_slots
        (J,))`` copies the listed KV blocks *between two pools* across
        every leaf with a capacity axis, and the listed per-slot
        recurrent-state rows across the leaves without one — the device
        half of moving a dying clone's in-flight requests to a survivor.
        Padding follows the serving conventions: block id 0 is the trash
        block on both sides (a 0→0 pad copy is a no-op) and an
        out-of-range destination slot drops its state-row write.

        ``compress=True`` is the compressed KV transfer of ADR-009: the
        gathered blocks round-trip through per-(block, head) int8
        quantization (``ops.quantize_kv_blocks``) before landing in the
        destination pool — the device realization of shipping the int8
        payload + scales over the inter-clone link, so decode on the
        receiving clone genuinely runs on dequantized KV."""
        fns = getattr(self, "_migrate_fns", None)
        if fns is None:
            fns = self._migrate_fns = {}
        if fns.get(compress) is None:
            b_ax, c_ax = self._batch_axis, self._cap_axis

            def migrate(dst_pool, src_pool, src_ids, dst_ids,
                        src_slots, dst_slots):
                def mv(dleaf, sleaf, bax, cax):
                    if cax is None:          # per-slot state rows
                        d = jnp.moveaxis(dleaf, bax, 0)
                        s = jnp.moveaxis(sleaf, bax, 0)
                        return jnp.moveaxis(
                            d.at[dst_slots].set(s[src_slots], mode="drop"),
                            0, bax)
                    d = jnp.moveaxis(dleaf, bax, 0)
                    s = jnp.moveaxis(sleaf, bax, 0)
                    payload = s[src_ids]
                    if compress:
                        q, sc = kops.quantize_kv_blocks(payload)
                        payload = kops.dequantize_kv_blocks(
                            q, sc, dtype=dleaf.dtype)
                    return jnp.moveaxis(d.at[dst_ids].set(payload),
                                        0, bax)

                return jax.tree.map(mv, dst_pool, src_pool, b_ax, c_ax)

            fns[compress] = jax.jit(migrate)
        return fns[compress]


def measured_executor(clone, fn, args):
    """ClientHandler executor that runs each dispatch exactly once and
    bills the wall seconds it took, up to ``jax.block_until_ready`` — no
    re-run, no modeled FLOP ratio.  For runs on the device."""
    del clone
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


class ServingEngine:
    """Batched prefill + decode with ThinkAir placement decisions."""

    def __init__(self, cfg, *, policy: Policy = Policy.EXEC_TIME,
                 link: str = "wifi-local", max_batch: int = 8,
                 capacity: int = 256, backend: Optional[LMBackend] = None):
        self.cfg = cfg
        self.max_batch = max_batch
        self.capacity = capacity
        self.backend = backend or LMBackend(cfg, capacity)
        self.params = self.backend.params
        self.ec = ExecutionController(policy=policy, link=link)
        self.ec.pool.provision("main", 8)       # paused secondaries (paper)
        backend_ = self.backend

        # KV working set drives escalation: bytes ~ cache size
        def prefill_mem(params, tokens):
            return backend_.cache_mem_bytes(tokens.shape[0])

        self.rm_prefill = RemoteableMethod(
            "serve_prefill", self.backend.prefill, jit=False,
            size_fn=lambda p, t: t.size,
            split_fn=self._split_prefill, merge_fn=self._merge_prefill,
            mem_fn=prefill_mem)
        self.rm_decode = RemoteableMethod(
            "serve_decode", self.backend.decode, jit=False,
            size_fn=lambda p, c, t, pos: t.shape[0])
        self.stats = {"requests": 0, "batches": 0, "offloaded": 0,
                      "escalations": 0}

    @staticmethod
    def _split_prefill(args, k):
        params, tokens = args
        tok_shards = np.array_split(np.asarray(tokens), k, axis=0)
        return [(params, jnp.asarray(t)) for t in tok_shards]

    @staticmethod
    def _merge_prefill(values):
        toks = jnp.concatenate([v[0] for v in values], axis=0)
        caches = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                              *[v[1] for v in values])
        return toks, caches

    def serve_batch(self, reqs: List[Request], *, n_clones: int = 1,
                    force: Optional[str] = None) -> List[Completion]:
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((len(reqs), plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :len(r.prompt)] = r.prompt
        res_p = self.ec.execute(self.rm_prefill, self.params,
                                jnp.asarray(toks), n_clones=n_clones,
                                force=force)
        next_tok, cache = res_p.value
        out = [list() for _ in reqs]
        steps_needed = max(r.max_new_tokens for r in reqs)
        tok = next_tok[:, None]
        total_time = res_p.time_s
        decode_venue = "-"
        # per-batch aggregation over prefill AND every decode step
        offloaded = int(res_p.offloaded)
        escalations = res_p.escalations
        for step_i in range(steps_needed):
            for i in range(len(reqs)):
                out[i].append(int(tok[i, 0]))
            pos = jnp.int32(min(plen + step_i, self.capacity - 1))
            res_d = self.ec.execute(self.rm_decode, self.params, cache, tok,
                                    pos, force=force)
            tok, cache = res_d.value
            tok = tok[:, None]
            total_time += res_d.time_s
            decode_venue = res_d.venue
            offloaded += int(res_d.offloaded)
            escalations += res_d.escalations
        self.stats["requests"] += len(reqs)
        self.stats["batches"] += 1
        self.stats["offloaded"] += offloaded
        self.stats["escalations"] += escalations
        return [Completion(r.rid, out[i], res_p.venue, decode_venue,
                           total_time, escalations)
                for i, r in enumerate(reqs)]


# --------------------------------------------------------------------------- #
# Event-driven Client Handler (continuous batching + elastic clones)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class _Cohort:
    """Requests admitted at one step boundary, decoding in lockstep.

    This is the *contiguous* (legacy, ``kv="contiguous"``) batching unit:
    one shared position cursor, so only requests admitted at the same step
    boundary fuse, and late arrivals wait for a clone to free up.  The paged
    path (:class:`_SlotEngine`) replaces it as the default.
    """

    reqs: List[ServeRequest]
    clone: object
    plen: int
    outs: List[List[int]] = dataclasses.field(default_factory=list)
    first_token_t: List[float] = dataclasses.field(default_factory=list)
    token_ts: List[List[float]] = dataclasses.field(default_factory=list)
    cache: object = None
    tok: object = None
    step: int = 0
    phase: str = "prefill"


class PoolExhausted(RuntimeError):
    """Raised by the allocator when no block can be produced — the signal
    the serving layer converts into a preemption (ADR-003), never a
    crash."""


class KVBlockPool:
    """Host-side paged-KV bookkeeping for one engine (one clone).

    Owns the device block pool plus the block table, per-slot decode
    cursors, per-block *refcounts*, and a content-addressed **prefix
    index** (ADR-003).  Block id 0 is the *trash block*: it is never
    allocated, every inactive slot's table points at it, so decode writes
    from idle rows land somewhere harmless.

    Refcounted sharing: a block may appear in several slots' tables at
    once (``ref[b]`` = number of table references).  ``free_slot`` only
    decrements; a block returns to circulation at refcount zero — and if
    it is a *prompt* block recorded in the prefix index it parks on the
    ``cached-free`` list (still resident, LRU-evicted only when a fresh
    block is needed), so a later request with the same prompt prefix maps
    it back at refcount 1 instead of re-prefilling.

    The prefix index is a trie over full token blocks: node = physical
    block id, edge = that block's ``block_size`` token tuple under its
    parent (root = -1).  Admission walks the trie (``match_prefix``),
    maps every fully-matched block shared, and handles the *first
    divergent block* by copy-on-write: a cached block whose first ``rem``
    tokens match the remaining prompt is copied on device into a fresh
    private block (``ops.copy_blocks``) which the slot then overwrites
    from position ``cached_len`` on, leaving the shared source intact.

    Admission reserves only the *prompt's* private blocks (optimistic —
    no worst-case commitment); decode growth that exhausts the pool
    raises :class:`PoolExhausted`, which the engine resolves by
    preempting a victim slot instead of failing the request.
    """

    def __init__(self, backend, max_slots: int, block_size: int,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True):
        self.backend = backend
        self.bs = block_size
        self.max_slots = max_slots
        self.capacity = backend.capacity
        self.max_blk = -(-backend.capacity // block_size)
        # default pool provisions worst case (+1 for the trash block);
        # benchmarks may size it tighter — preemption absorbs the squeeze
        self.num_blocks = num_blocks or max_slots * self.max_blk + 1
        self.pool = backend.init_paged_pool(max_slots, self.num_blocks,
                                            block_size)
        self.prefix_cache = prefix_cache
        self.tables = np.zeros((max_slots, self.max_blk), np.int32)
        self.pos = np.zeros((max_slots,), np.int32)
        self.active = np.zeros((max_slots,), bool)
        self.n_blocks_of = np.zeros((max_slots,), np.int32)
        self.ref = np.zeros((self.num_blocks,), np.int32)
        # bumped on every host-side table mutation; _SlotEngine caches the
        # device copy of ``tables`` against it (re-upload only when dirty)
        self.tables_version = 0
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._free_blocks = list(range(self.num_blocks - 1, 0, -1))
        # prefix index: trie node = block id; root parent = -1
        self._children: Dict[int, Dict[tuple, int]] = {}
        self._node: Dict[int, tuple] = {}        # bid -> (parent, tokens)
        self._cached_free: Dict[int, None] = {}  # ref==0, indexed (LRU)
        # blocks whose cached content lands via an in-flight suffix scan:
        # not yet readable by a same-round sharer (see _submit_engine_step)
        self._pending: set = set()
        # cached-free blocks serving as CoW sources this round: eviction
        # must not recycle them before the device copy reads them
        self._hold: set = set()
        # trie nodes created by each slot's admission, until its prefill
        # completes: a *cancelled* admission must unindex exactly these
        # (their device content was never written)
        self._fresh_nodes: Dict[int, List[int]] = {}
        self.stats = {"hit_tokens": 0, "prompt_tokens": 0,
                      "cow_copies": 0, "evictions": 0}

    def reset(self) -> None:
        """Release every slot for engine reuse, *keeping the prefix
        index*: the device pool is retained as-is, so indexed blocks stay
        valid cached KV across engine generations on the same clone —
        that persistence is what lets serial (non-overlapping) requests
        still share a system prompt.  Stale content in unindexed blocks
        is harmless: prefill fully overwrites a slot's fresh blocks
        before any read and positions past a cursor are always masked."""
        for slot in range(self.max_slots):
            if self.n_blocks_of[slot]:
                self.free_slot(slot)
        self.pos[:] = 0
        self.active[:] = False
        self.tables_version += 1
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._pending.clear()
        self._hold.clear()

    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    def _need_blocks(self, prompt_len: int, max_new_tokens: int) -> int:
        total = min(prompt_len + max_new_tokens, self.capacity)
        return min(-(-max(total, prompt_len) // self.bs), self.max_blk)

    def available_blocks(self) -> int:
        """Blocks allocatable right now: free plus cached-but-unreferenced
        (the latter evict from the prefix index on demand)."""
        return len(self._free_blocks) + len(self._cached_free)

    # ------------------------------------------------------------ prefix
    def match_prefix(self, prompt: np.ndarray):
        """Longest cached prefix of ``prompt`` (pure — no state change).

        Returns ``(shared_ids, cow_src, cached_len)``: ``shared_ids`` are
        resident full blocks covering ``prompt[:len(shared_ids) * bs]``,
        ``cow_src`` is the first-divergent-block copy-on-write source (a
        cached block whose leading tokens extend the match partway), and
        ``cached_len`` the total matched token count.  The match is
        capped at ``len(prompt) - 1``: at least one suffix token is
        always re-prefilled, because the *logits* after the last prompt
        token (the row's first generated token) are not cached."""
        shared: List[int] = []
        cow_src = None
        c = 0
        if not self.prefix_cache:
            return shared, cow_src, 0
        p = len(prompt)
        parent = -1
        while c + self.bs <= p - 1:
            tok = tuple(int(t) for t in prompt[c:c + self.bs])
            b = self._children.get(parent, {}).get(tok)
            if b is None or b in self._pending:
                break
            shared.append(b)
            parent = b
            c += self.bs
        rem_cap = min(p - 1 - c, self.bs)
        if rem_cap > 0:
            want = tuple(int(t) for t in prompt[c:c + rem_cap])
            best_m = 0
            for tok, b in self._children.get(parent, {}).items():
                if b in self._pending:
                    continue
                m = 0
                while m < rem_cap and tok[m] == want[m]:
                    m += 1
                if m > best_m:      # ties: first-inserted child wins
                    best_m, cow_src = m, b
            c += best_m
        return shared, cow_src, c

    def _index_prompt(self, slot: int, prompt: np.ndarray,
                      n_shared: int, via_suffix: bool) -> None:
        """Record the slot's fully-covered prompt blocks as trie nodes.
        Blocks whose content arrives via an in-flight suffix scan are
        marked pending — unreadable by same-round sharers."""
        if not self.prefix_cache:
            return
        parent = -1
        created = self._fresh_nodes.setdefault(slot, [])
        for i in range(len(prompt) // self.bs):
            tok = tuple(int(t) for t in prompt[i * self.bs:
                                               (i + 1) * self.bs])
            kids = self._children.setdefault(parent, {})
            b = kids.get(tok)
            if b is None:
                b = int(self.tables[slot, i])
                kids[tok] = b
                self._node[b] = (parent, tok)
                created.append(b)
                if via_suffix and i >= n_shared:
                    self._pending.add(b)
            parent = b

    def clear_pending(self) -> None:
        """Called when a submitted step folds back: every suffix-written
        block's device content is now real (shareable), and in-flight CoW
        sources have been copied (evictable again)."""
        self._pending.clear()
        self._hold.clear()

    def _unindex(self, bid: int) -> None:
        """Drop ``bid`` from the trie.  Its cached descendants become
        unreachable (their chain is broken), so they are unindexed too
        and — when unreferenced — recycled straight to the free list."""
        parent, tok = self._node.pop(bid)
        kids = self._children.pop(bid, {})
        d = self._children.get(parent)
        if d is not None:
            d.pop(tok, None)
            if not d and parent != -1:
                del self._children[parent]
        self._pending.discard(bid)
        for child in kids.values():
            self._unindex(child)
            if self.ref[child] == 0 and child in self._cached_free:
                del self._cached_free[child]
                self._free_blocks.append(child)

    # ------------------------------------------------------- block alloc
    def _alloc_block(self) -> int:
        """A private block: free list first, then LRU-evict a cached-free
        block out of the prefix index; ``PoolExhausted`` when every block
        is referenced by a live slot."""
        evictable = (b for b in self._cached_free if b not in self._hold)
        if self._free_blocks:
            b = self._free_blocks.pop()
        elif (b := next(evictable, None)) is not None:  # LRU: oldest first
            del self._cached_free[b]
            self._unindex(b)
            self.stats["evictions"] += 1
        else:
            raise PoolExhausted(
                "KV block pool exhausted: all "
                f"{self.num_blocks - 1} blocks referenced by live slots "
                "(the engine preempts a victim when this surfaces "
                "mid-decode; a single request whose context exceeds the "
                "pool cannot be served — raise num_blocks)")
        self.ref[b] = 1
        return b

    def _ref_inc(self, bid: int) -> None:
        if self.ref[bid] == 0:
            self._cached_free.pop(bid, None)      # resurrected from cache
        self.ref[bid] += 1

    def can_admit(self, prompt, max_new_tokens: int = 0) -> bool:
        """True when a request's *prompt* fits now: a free slot plus
        enough allocatable blocks for its non-shared prompt blocks.
        ``prompt`` is the effective token array (prefix matching applies)
        or a bare length (no matching — the worst case).  Decode growth
        is not reserved: exhaustion mid-decode preempts a victim instead
        of being pre-gated, which is what keeps a tight pool admitting
        work instead of serializing on worst-case commitments."""
        if not self._free_slots:
            return False
        if isinstance(prompt, (int, np.integer)):
            p, n_shared, n_spoken_for = int(prompt), 0, len(self._hold)
        else:
            p = len(prompt)
            shared, cow_src, _ = self.match_prefix(prompt)
            n_shared = len(shared)
            # cached-free blocks this admission would *resurrect* or hold
            # as its CoW source (and already-held sources) can't also
            # serve the private need
            n_spoken_for = (sum(1 for b in shared if self.ref[b] == 0)
                            + sum(1 for b in self._hold
                                  if b in self._cached_free
                                  and b not in shared))
            if (cow_src is not None and self.ref[cow_src] == 0
                    and cow_src not in self._hold):
                n_spoken_for += 1
        nb0 = -(-p // self.bs)
        return self.available_blocks() - n_spoken_for >= nb0 - n_shared

    def used_blocks(self) -> int:
        """Blocks referenced by live slots (cached-free excluded: they
        are reclaimable, so they don't count against utilization)."""
        return int((self.ref[1:] > 0).sum())

    def written_tokens(self) -> int:
        """Logical tokens resident across slots (each slot counts its
        full context, so shared prefixes count once *per sharer* — the
        paged report divides this by physical reservation, and a ratio
        above 1.0 is exactly the prefix cache's memory win)."""
        return int(self.pos[self.active | (self.pos > 0)].sum())

    def alloc_slot(self, prompt, max_new_tokens: int = 0,
                   force_suffix: bool = False):
        """Claim a free slot for ``prompt`` (token array, or bare length
        to bypass prefix matching); cursor starts at the prompt length.

        Matches the prompt against the prefix index: fully-matched blocks
        enter the table shared (refcount + 1), the first divergent block
        is claimed as a fresh private block to be copied-on-write from
        ``cow_pair[0]``, and the remaining prompt blocks are fresh
        private allocations.  Returns ``(slot, new_ids, cached_len,
        cow_pair)``: ``new_ids`` are the blocks a *full* prefill must
        write (all of them when ``cached_len == 0``), ``cow_pair`` is
        ``(src, dst)`` or None.  ``force_suffix`` marks the row as
        suffix-prefilled regardless of match (restores), so its indexed
        blocks stay pending until the step folds."""
        if isinstance(prompt, (int, np.integer)):
            p = int(prompt)
            shared, cow_src, cached_len = [], None, 0
            indexable = False
        else:
            prompt = np.asarray(prompt, np.int32)
            p = len(prompt)
            shared, cow_src, cached_len = self.match_prefix(prompt)
            indexable = True
        slot = self._free_slots.pop()
        nb0 = -(-p // self.bs)
        for b in shared:
            self._ref_inc(b)
        cow_pair = None
        new_ids = []
        row = list(shared)
        if cow_src is not None:
            if self.ref[cow_src] == 0:
                # cached-free source: shield it from LRU eviction until
                # the round's device copy has read it (clear_pending)
                self._hold.add(cow_src)
            dst = self._alloc_block()
            cow_pair = (cow_src, dst)
            row.append(dst)
            self.stats["cow_copies"] += 1
        while len(row) < nb0:
            b = self._alloc_block()
            new_ids.append(b)
            row.append(b)
        self.tables[slot, :] = 0
        self.tables[slot, :nb0] = row
        self.pos[slot] = p
        self.n_blocks_of[slot] = nb0
        self.tables_version += 1
        if indexable:
            self._index_prompt(slot, prompt, len(shared),
                               via_suffix=force_suffix or cached_len > 0)
            self.stats["hit_tokens"] += cached_len
            self.stats["prompt_tokens"] += p
        return slot, np.asarray(new_ids, np.int32), cached_len, cow_pair

    def grow_for_window(self, counts) -> None:
        """Before a decode window: every active slot must own every block
        its next ``counts[slot]`` token writes land in (the window may
        cross several block boundaries, so the whole window's blocks are
        reserved up front — the scan cannot call back into the allocator
        mid-flight).  Write positions clamp at ``capacity - 1`` exactly
        like the decode path, so a window running past capacity needs no
        block beyond the last.  Raises :class:`PoolExhausted` when a
        block cannot be produced — the engine's preemption trigger; the
        call is resumable after a victim frees blocks (already-grown
        slots are skipped on re-entry)."""
        for slot in np.nonzero(self.active)[0]:
            n = int(counts[slot])
            if n <= 0:
                continue
            last = min(int(self.pos[slot]) + n - 1, self.capacity - 1)
            top = min(last // self.bs, self.max_blk - 1)
            while int(self.n_blocks_of[slot]) <= top:
                blk_i = int(self.n_blocks_of[slot])
                self.tables[slot, blk_i] = self._alloc_block()
                self.n_blocks_of[slot] = blk_i + 1
                self.tables_version += 1

    def grow_for_write(self) -> None:
        """One-token lookahead: the per-token decode path's pre-step grow."""
        self.grow_for_window(self.active.astype(np.int32))

    def free_slot(self, slot: int) -> None:
        """Retire (or preempt) a slot: decrement each referenced block,
        zero its table row (trash).  A block reaching refcount zero
        returns to the free list — or, when it is a prompt block in the
        prefix index, parks cached-free so the next same-prefix request
        restores it for free."""
        for j in range(int(self.n_blocks_of[slot])):
            b = int(self.tables[slot, j])
            self.ref[b] -= 1
            if self.ref[b] == 0:
                if b in self._node:
                    self._cached_free[b] = None       # LRU tail
                else:
                    self._free_blocks.append(b)
        self.tables[slot, :] = 0
        self.pos[slot] = 0
        self.active[slot] = False
        self.n_blocks_of[slot] = 0
        self.tables_version += 1
        self._fresh_nodes.pop(slot, None)   # prefill completed: nodes stay
        self._free_slots.append(slot)

    def cancel_slot(self, slot: int) -> None:
        """Undo an admission whose prefill never ran (join rollback):
        the trie nodes this admission created point at blocks whose
        device content was never written, so they must leave the index
        before the blocks recirculate — a later match against them would
        serve garbage KV.  Resurrected shared blocks (valid content from
        an earlier prefill) stay indexed and simply return cached-free."""
        for b in self._fresh_nodes.get(slot, ()):
            if b in self._node:             # may already be unindexed
                self._unindex(b)            # (recursion / LRU eviction)
        self.free_slot(slot)


@dataclasses.dataclass
class _Slot:
    """One request occupying one decode slot of a :class:`_SlotEngine`.

    ``token_ts`` mirrors ``out``: the streamed delivery timestamp of each
    emitted token (window folds interpolate within the dispatch interval
    — ADR-007 per-tenant TTFT/TPOT)."""

    req: ServeRequest
    out: List[int]
    first_token_t: float = 0.0
    token_ts: List[float] = dataclasses.field(default_factory=list)


def _carried_ts(req: ServeRequest, n: int) -> List[float]:
    """Delivery stamps carried across preempt/migrate/restore, clamped
    to ``n`` tokens and padded with the TTFT stamp when a legacy carrier
    did not record them."""
    ts = list(req.token_ts[:n])
    pad = req.first_token_t if req.first_token_t is not None else 0.0
    return ts + [pad] * (n - len(ts))


class _SlotEngine:
    """A clone's decode loop: max_batch slots over one paged KV pool.

    The engine replaces the cohort as the unit of batching.  A request is
    *admitted* into any free slot (``admit``) at any time — including while
    a decode step is in flight — and its prefill is folded into the next
    step, so late arrivals join mid-flight instead of waiting for the next
    cohort.  Slots retire independently at step granularity; their blocks
    return to the pool with no cache re-gather.
    """

    def __init__(self, backend, clone, kv: KVBlockPool, window: int = 1,
                 donate: bool = False, chunk: int = 0, mixed: bool = False):
        self.backend = backend
        self.clone = clone
        self.kv = kv
        self.window = window
        self.donate = donate
        # chunked suffix prefill: C tokens per scan step (0 = stepwise);
        # mixed: fold the suffix scan INTO the decode window's scan so a
        # join/restore never stalls the decode cohort (ADR-005)
        self.chunk = chunk
        self.mixed = mixed
        # decode_slots (the per-token fn) is deliberately unused here: the
        # engine always dispatches windows (window=1 == one-step window);
        # benchmarks/decode_micro.py is the per-token fn's only caller
        self.prefill_into, _, self.decode_window = \
            backend.paged_fns(kv.bs, window, donate)
        self.slots: List[Optional[_Slot]] = [None] * kv.max_slots
        self.tok_host = np.zeros((kv.max_slots,), np.int32)
        self.joins: List[tuple] = []        # (slot, req, toks, blk_ids)
        self.sfx_joins: List[tuple] = []    # (slot, req, sfx, pos0, restore)
        self.cow_pairs: List[tuple] = []    # (slot, src, dst) this round
        # inbound KV migrations from a dying clone (ADR-006):
        # (dst_slot, req, out, first_token_t, src_pool, src_ids, dst_ids,
        #  src_slot, pos) — folded into the next step as a device copy
        self.migrations: List[tuple] = []
        self.submitted_joins: List[tuple] = []
        self.submitted_sfx: List[tuple] = []
        self.submitted_migrations: List[tuple] = []
        self.decode_rows: Optional[np.ndarray] = None
        self.decode_counts: Optional[np.ndarray] = None
        self._tables_dev = None             # device tables cache
        self._tables_ver = -1
        # speculative decoding (ADR-008): the paired cheap-tier draft
        # clone, the draft model's own paged pool (SAME block tables as
        # ``kv``), per-slot draft-pool cursors (``dpos[i] <= kv.pos[i]``;
        # the gap is the committed history the next catch-up replays),
        # the stashed verify builder for the round in flight, and the
        # (drafts, n_spec) pending host-side acceptance.  ``spec_on``
        # goes (stickily) False when the draft dies or acceptance
        # collapses — the engine degrades to plain window decode.
        self.spec_on = False
        self.draft_clone = None
        self.draft_pool = None
        self.spec_k = 0
        self.dpos = np.zeros((kv.max_slots,), np.int32)
        self._verify_builder = None
        self._spec_round: Optional[np.ndarray] = None   # k per row, in flight
        self.spec_pending: Optional[tuple] = None
        self.spec_rounds_done = 0
        # disaggregated prefill (ADR-009): the paired large-tier prefill
        # clone and its scratch pool, rows awaiting the partner dispatch
        # (``disagg_joins``) or riding one (``submitted_disagg``), and the
        # decode-pool blocks still waiting for their streamed KV —
        # re-marked pending after every fold so no sharer attends over
        # them before the handoff copy lands.  ``disagg_on`` goes False
        # when the partner dies (degrade to co-located, never a stall).
        self.disagg_on = False
        self.prefill_clone = None
        self.prefill_pool: Optional[KVBlockPool] = None
        self.disagg_joins: List[tuple] = []       # (slot, req, eff, new_ids)
        self.submitted_disagg: List[tuple] = []
        self.disagg_blocks: Dict[int, List[int]] = {}
        # one main (step) task and at most one partner prefill task may
        # be in flight concurrently; the run loop pumps whichever side
        # has work and is idle
        self.main_inflight = False
        self.step_id = 0            # the handler's id of the last step built
        self.disagg_inflight = False

    def device_tables(self):
        """Device copy of ``kv.tables``, re-uploaded only when the host
        table has been dirtied since the last step (alloc/grow/free/reset
        all bump ``tables_version``)."""
        if self._tables_ver != self.kv.tables_version:
            self._tables_dev = jnp.asarray(self.kv.tables)
            self._tables_ver = self.kv.tables_version
        return self._tables_dev

    @staticmethod
    def effective_prompt(req: ServeRequest, prompt_pad: int,
                         capacity: int) -> np.ndarray:
        """The token sequence a slot's prefill must make resident.

        Fresh request: the prompt zero-padded to ``prompt_pad`` (padding
        tokens are context, exactly like the batched prefill path).  A
        preempted request restoring: padded prompt plus every generated
        token *except the last* — the last emitted token's KV was never
        written (it is the next decode input), so the restored cursor
        lands exactly where the preempted one stood.  The trailing
        ``[:capacity]`` clamp is a last resort for past-capacity victims
        (their last-cell overwrite history cannot be replayed anyway);
        ``_grow_or_preempt`` avoids choosing them while any in-capacity
        victim exists."""
        base = np.zeros((prompt_pad,), np.int32)
        base[:min(len(req.prompt), prompt_pad)] = req.prompt[:prompt_pad]
        if not req.generated:
            return base
        eff = np.concatenate(
            [base, np.asarray(req.generated[:-1], np.int32)])
        return eff[:capacity]

    def admit(self, req: ServeRequest, prompt_pad: int) -> dict:
        """Claim a slot + blocks; route the row to the batched full
        prefill (no cached prefix) or the suffix scan (prefix hit or
        preemption restore).  Returns admission stats for the handler."""
        restore = bool(req.generated)
        eff = self.effective_prompt(req, prompt_pad, self.kv.capacity)
        slot, new_ids, cached_len, cow = self.kv.alloc_slot(
            eff, req.max_new_tokens, force_suffix=restore)
        if cow is not None:
            self.cow_pairs.append((slot,) + cow)
        if restore or cached_len > 0:
            sfx = eff[cached_len:]
            self.sfx_joins.append((slot, req, sfx, cached_len, restore))
            return {"cached": cached_len, "suffix": len(sfx),
                    "restore": restore, "prompt": len(eff)}
        self.joins.append((slot, req, jnp.asarray(eff[None]),
                           jnp.asarray(new_ids)))
        return {"cached": 0, "suffix": 0, "restore": False,
                "prompt": len(eff)}

    def alive(self) -> bool:
        return (any(s is not None for s in self.slots)
                or bool(self.joins) or bool(self.sfx_joins)
                or bool(self.migrations) or bool(self.disagg_joins)
                or bool(self.submitted_disagg))

    def step_work(self) -> bool:
        """Does the engine have anything for its *own* clone to run right
        now?  Rows parked on the disagg partner are excluded — an alive
        engine with only those in flight waits for the handoff instead of
        dispatching an empty step."""
        return (bool(self.kv.active.any()) or bool(self.joins)
                or bool(self.sfx_joins) or bool(self.migrations))


@dataclasses.dataclass
class ServeReport:
    """One ``ClientHandler.run`` outcome: latency, elasticity, KV economy.

    ``kv_util`` is the time-averaged fraction of *reserved* KV memory that
    holds written tokens (sampled at every decode submission); contiguous
    cohorts reserve ``rows x capacity`` up front while the paged pool only
    reserves allocated blocks.  With prefix sharing every sharer counts
    its full logical context, so ``kv_util`` above 1.0 means shared
    blocks are serving more logical tokens than their physical size — the
    prefix cache's memory win.  ``kv_reserved_peak`` is the peak physical
    reservation in tokens.  ``prefix_hit_rate`` is cached prompt tokens /
    total prompt tokens over all admissions (restores included);
    ``preemptions`` counts slot evictions under pool pressure and
    ``restored_tokens`` the tokens re-prefilled bringing victims back.

    Fleet economics (ADR-004): ``fleet_mix`` counts completions per clone
    type, ``escalations`` the requests whose KV demand forced a bigger
    tier, ``clone_seconds_by_type`` the RUNNING clone-seconds billed per
    tier (idle-but-running time included — that is what TTL pausing
    saves), ``cost_usd`` their on-demand $ total, ``energy_j_by_type``
    the chips-aware busy energy per tier, and ``power_offs`` the clones
    the OFF_IDLE_TTL actually powered off.
    """

    completions: List[ServeCompletion]
    accepted: int
    rejected: int
    makespan_s: float
    p50_latency_s: float
    p99_latency_s: float
    p50_ttft_s: float
    tokens_per_s: float
    peak_secondaries: int
    scale_ups: int
    busy_energy_j: float
    pool_stats: Dict
    clone_samples: List[tuple]
    kv_mode: str = "paged"
    kv_util: float = 0.0
    kv_reserved_peak: int = 0
    prefix_hit_rate: float = 0.0
    preemptions: int = 0
    restored_tokens: int = 0
    fleet_mix: Dict[str, int] = dataclasses.field(default_factory=dict)
    escalations: int = 0
    clone_seconds_by_type: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    cost_usd: float = 0.0
    energy_j_by_type: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    power_offs: int = 0
    # fault tolerance (ADR-006): ``faults_injected`` counts fired clone
    # faults (kills + drains + slowdowns), ``recoveries_migrated`` the
    # in-flight requests whose KV blocks moved to a survivor's pool,
    # ``recoveries_restored`` those requeued for prefix-accelerated
    # re-prefill, ``hedges_fired``/``hedge_wins`` the straggler decode
    # windows raced on a second clone and the races the duplicate won,
    # ``breaker_opens`` the fleet-wide circuit-breaker open transitions
    faults_injected: int = 0
    recoveries_migrated: int = 0
    recoveries_restored: int = 0
    hedges_fired: int = 0
    hedge_wins: int = 0
    breaker_opens: int = 0
    # gateway SLO telemetry (ADR-007): ``slo_attainment`` maps SLO class
    # -> fraction of *offered* requests in that class that were served
    # inside their deadline (no-deadline completions count as met;
    # gateway-rejected/shed/dropped work counts as missed — honesty under
    # overload), ``goodput_tps`` counts only deadline-meeting delivered
    # tokens per second (cache hits included: they are real deliveries),
    # ``gateway_shed``/``gateway_rejected`` the bounded-backlog evictions
    # and predictive up-front rejections, ``gateway_retries`` scheduled
    # Retry-After replays, ``cache_hits`` responses served from the
    # gateway's exact-match LRU, ``shed_by_slo`` sheds per class (must
    # never contain "interactive"), ``per_tenant`` served/p50 TTFT/p50
    # TPOT per tenant, ``peak_queue_depth`` the deepest handler admission
    # queue observed (the divergence metric for ungated overload)
    slo_attainment: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    goodput_tps: float = 0.0
    gateway_shed: int = 0
    gateway_rejected: int = 0
    gateway_retries: int = 0
    cache_hits: int = 0
    shed_by_slo: Dict[str, int] = dataclasses.field(default_factory=dict)
    per_tenant: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    peak_queue_depth: int = 0
    # speculative decoding (ADR-008): ``spec_rounds`` counts completed
    # draft+verify rounds, ``spec_tokens`` the tokens emitted through
    # them (lossless: token-identical to plain greedy decode),
    # ``acceptance_rate`` accepted / proposed draft tokens, and
    # ``spec_fallbacks`` the engines that degraded to plain decode —
    # draft-clone death or acceptance collapse, never a stall
    spec_rounds: int = 0
    spec_tokens: int = 0
    acceptance_rate: float = 0.0
    spec_fallbacks: int = 0
    # disaggregated prefill/decode (ADR-009): ``disagg_handoffs`` counts
    # prompts prefilled on the partner tier whose KV blocks migrated to
    # the decode clone, ``disagg_colocated`` the long-prompt candidates
    # the transfer-cost planner kept local, ``disagg_fallbacks`` the
    # engines that degraded to co-located prefill (no partner available
    # or partner death), ``kv_transfer_bytes``/``kv_transfer_s`` the
    # modeled cross-clone KV handoff traffic (compressed transfers bill
    # the int8 payload + scales), and ``per_clone`` the per-clone routing
    # telemetry: prefix hit rate and KV transfer volume per clone id.
    disagg_handoffs: int = 0
    disagg_colocated: int = 0
    disagg_fallbacks: int = 0
    kv_transfer_bytes: float = 0.0
    kv_transfer_s: float = 0.0
    per_clone: Dict[str, Dict[str, object]] = dataclasses.field(
        default_factory=dict)

    def summary(self) -> str:
        """One-line digest (documented in docs/benchmarks.md)."""
        return (f"served={len(self.completions)} shed={self.rejected} "
                f"p50={self.p50_latency_s:.3f}s p99={self.p99_latency_s:.3f}s "
                f"ttft50={self.p50_ttft_s:.3f}s "
                f"tok/s={self.tokens_per_s:.1f} "
                f"kv={self.kv_mode} kv_util={self.kv_util:.0%} "
                f"prefix_hits={self.prefix_hit_rate:.0%} "
                f"preempt={self.preemptions} "
                f"peak_secondaries={self.peak_secondaries}")


class ClientHandler:
    """Event-driven continuous-batching server on an elastic clone pool.

    ``kv="paged"`` (default): each clone runs a :class:`_SlotEngine` —
    ``max_batch`` slots over a :class:`KVBlockPool`, per-slot decode
    cursors, mid-flight admission into free slots.  ``kv="contiguous"``
    keeps the PR-1 cohort path (shared cursor, step-boundary fusion only)
    as the measurable baseline for the paged design.
    """

    def __init__(self, backend, *, link: str = "wifi-local",
                 clone_type: str = "main", max_batch: int = 4,
                 queue_depth: int = 64, max_secondaries: int = 8,
                 min_secondaries: int = 0, work_per_clone: int = 1,
                 prompt_pad: int = 8, use_primary: bool = True,
                 provision_paused: bool = True,
                 kv: str = "paged", block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 decode_window: int = 1, donate_kv: bool = False,
                 prefill_chunk: Optional[int] = None,
                 mixed_dispatch: Optional[bool] = None,
                 fleet: Optional[List[str]] = None,
                 placement_policy: Policy = Policy.EXEC_TIME_AND_ENERGY,
                 energy_model: Optional[TpuEnergyModel] = None,
                 provision: Optional[Dict[str, int]] = None,
                 executor: Optional[Callable] = None,
                 pool: Optional[ClonePool] = None,
                 clock: Optional[VirtualClock] = None,
                 faults: Optional[List[CloneFault]] = None,
                 hedge_factor: float = 0.0,
                 hedge_quantile: float = 0.95,
                 hedge_min_samples: int = 8,
                 gateway: Optional[StreamingGateway] = None,
                 breaker_max_open_s: Optional[float] = None,
                 breaker_max_probes: Optional[int] = None,
                 speculative: bool = False, spec_k: int = 4,
                 spec_corruption: float = 0.0,
                 draft_cost: Optional[float] = None,
                 routing: str = "ledger",
                 disagg: bool = False, disagg_link: str = "ici",
                 disagg_compress: bool = False,
                 disagg_min_prompt: Optional[int] = None,
                 disagg_prefill_type: Optional[str] = None):
        if kv not in ("paged", "contiguous"):
            raise ValueError(f"kv must be 'paged' or 'contiguous': {kv!r}")
        if faults and kv != "paged":
            raise ValueError("fault injection requires kv='paged' — the "
                             "contiguous cohort keeps no per-slot restore "
                             "state, so a clone death would lose tokens")
        if hedge_factor > 0 and kv != "paged":
            raise ValueError("hedged dispatch races _SlotEngine decode "
                             "windows; it requires kv='paged'")
        if hedge_factor > 0 and donate_kv:
            raise ValueError("hedged dispatch re-runs the step closure on "
                             "a second clone; a donated KV pool is "
                             "consumed by the first run (ADR-002)")
        if decode_window < 1:
            raise ValueError(f"decode_window must be >= 1: {decode_window}")
        if decode_window > 1 and kv != "paged":
            raise ValueError("decode_window > 1 requires kv='paged' (the "
                             "contiguous cohort path decodes per token)")
        if donate_kv and executor is None:
            # the default Venue executor re-runs a closure to stabilize its
            # timing; a donated pool is consumed by the first run
            raise ValueError("donate_kv needs an executor that runs each "
                             "dispatch exactly once (the default venue "
                             "executor re-times cheap calls)")
        # cross-tier speculative decoding (ADR-008)
        if speculative:
            if kv != "paged":
                raise ValueError("speculative decoding scores draft "
                                 "windows through per-slot block tables; "
                                 "it requires kv='paged'")
            if donate_kv:
                raise ValueError("speculative decoding keeps the target "
                                 "pool alive across the draft round-trip; "
                                 "a donated pool is consumed (ADR-002)")
            if not getattr(backend, "supports_speculative", False):
                raise ValueError("speculative decoding needs a backend "
                                 "with a bound draft model "
                                 "(LMBackend(draft=...)) and chunked-"
                                 "verify support")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1: {spec_k}")
        # prefix-affinity / random routing (ADR-009): "ledger" keeps the
        # pure free-slot policy; "affinity" scores candidate engines by
        # prefix-index match depth on the incoming prompt; "random" is the
        # affinity sweep's control arm
        if routing not in ("ledger", "affinity", "random"):
            raise ValueError("routing must be 'ledger', 'affinity' or "
                             f"'random': {routing!r}")
        if routing != "ledger" and kv != "paged":
            raise ValueError("prefix-affinity/random routing scores the "
                             "paged prefix index; it requires kv='paged'")
        self.routing = routing
        # disaggregated prefill/decode (ADR-009)
        if disagg:
            if kv != "paged":
                raise ValueError("disaggregated prefill migrates paged KV "
                                 "blocks between clones; it requires "
                                 "kv='paged'")
            if not getattr(backend, "supports_chunked", False):
                raise ValueError("disaggregated prefill replays prompts "
                                 "through the chunked paged-prefill scan; "
                                 "the backend must support chunked prefill "
                                 "(all-attention, windowless layers)")
            if speculative:
                raise ValueError("disaggregated prefill and speculative "
                                 "decoding both pair the engine with a "
                                 "partner clone; run one at a time")
            if donate_kv:
                raise ValueError("disaggregated prefill keeps the partner "
                                 "pool alive across the handoff; a donated "
                                 "pool is consumed (ADR-002)")
            if disagg_link not in LINKS:
                raise ValueError(f"unknown disagg_link {disagg_link!r}; "
                                 f"known: {sorted(LINKS)}")
        self.disagg = disagg
        self.disagg_link = disagg_link
        self.disagg_compress = disagg_compress
        self.disagg_min_prompt = disagg_min_prompt
        self.speculative = speculative
        self.spec_k = spec_k
        self.spec_corruption = spec_corruption
        self.draft_cost = (draft_cost if draft_cost is not None
                           else getattr(backend, "draft_cost_ratio", 1.0))
        # chunked prefill / mixed dispatch (ADR-005): default ON whenever
        # the backend supports it (all-attention, windowless) and the KV
        # mode is paged; backends without the capability flag (test stubs)
        # keep the legacy stepwise path
        chunk_ok = kv == "paged" and bool(getattr(backend,
                                                  "supports_chunked", False))
        if prefill_chunk is None:
            prefill_chunk = 8 if chunk_ok else 0
        elif prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0: {prefill_chunk}")
        elif prefill_chunk > 0 and not chunk_ok:
            raise ValueError("prefill_chunk > 0 requires kv='paged' and a "
                             "backend with chunked-prefill support "
                             "(all-attention, windowless layers)")
        if mixed_dispatch is None:
            # speculative engines keep prefill and verify as separate
            # tiles of one closure (the verify window IS the decode
            # tile); the fused mixed scan has no verify variant
            mixed_dispatch = prefill_chunk > 0 and not speculative
        elif mixed_dispatch and speculative:
            raise ValueError("mixed_dispatch and speculative decoding are "
                             "mutually exclusive: the spec round's decode "
                             "tile is a verify window, not a decode scan")
        elif mixed_dispatch and prefill_chunk == 0:
            raise ValueError("mixed_dispatch requires prefill_chunk > 0 "
                             "(the fused step advances chunk tokens per "
                             "scan step)")
        self.prefill_chunk = prefill_chunk
        self.mixed_dispatch = mixed_dispatch
        self.kv_mode = kv
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.prefix_cache = prefix_cache
        self.decode_window = decode_window
        self.donate_kv = donate_kv
        self.backend = backend
        # breaker tuning (satellite of ADR-007): custom cooldown cap /
        # probe-chain cap for every clone the pool creates
        breaker_kwargs = {}
        if breaker_max_open_s is not None:
            breaker_kwargs["max_open_seconds"] = breaker_max_open_s
        if breaker_max_probes is not None:
            breaker_kwargs["max_probes"] = breaker_max_probes
        # one timeline: adopt a supplied pool's clock (TTL accounting and
        # dispatch must share it), otherwise build pool around ours
        if pool is not None:
            if not getattr(pool.clock, "virtual", False):
                raise TypeError("ClientHandler needs a pool on a "
                                "VirtualClock")
            if clock is not None and clock is not pool.clock:
                raise ValueError("pool and clock disagree — pass one "
                                 "timeline")
            self.clock = pool.clock
            self.pool = pool
            if breaker_kwargs:
                self.pool.breaker_kwargs.update(breaker_kwargs)
                for c in self.pool.clones:     # retrofit existing clones
                    c.breaker = CircuitBreaker(**self.pool.breaker_kwargs)
        else:
            self.clock = clock or VirtualClock()
            self.pool = ClonePool(link_name=link, clock=self.clock,
                                  max_clones=max_secondaries + 8,
                                  breaker_kwargs=breaker_kwargs)
        self.dispatcher = Dispatcher(self.pool, self.clock)
        self.queue = AdmissionQueue(queue_depth)
        # heterogeneous fleet (ADR-004): allowed tiers, rank-ascending;
        # the base clone_type is always a member, so fleet=None keeps the
        # exact homogeneous behaviour
        names = set(fleet or []) | {clone_type}
        self.fleet = sorted(names, key=lambda n: CLONE_TYPES[n].rank())
        self._fleet_set = set(self.fleet)
        self.energy = energy_model or TpuEnergyModel()
        self.placement = PlacementEngine(self.pool, fleet=self.fleet,
                                         policy=placement_policy,
                                         energy=self.energy)
        self.autoscaler = FleetAutoscaler(
            self.pool, self.placement, base_type=clone_type,
            work_per_clone=work_per_clone,
            min_secondaries=min_secondaries, max_secondaries=max_secondaries)
        if provision_paused:     # paper §5.3: secondaries pre-created paused
            self.pool.provision(clone_type, max_secondaries)
        for tname, n in (provision or {}).items():   # extra paused tiers
            self.pool.provision(tname, n)
        self.clone_type = clone_type
        self.max_batch = max_batch
        self.prompt_pad = prompt_pad
        self.use_primary = use_primary
        if not use_primary and max_secondaries < 1:
            raise ValueError("no primary and no secondaries: nothing can run")
        # executor(clone, fn, args) -> (value, venue_seconds); the default
        # runs on the clone's venue spec (tests inject fixed venue times)
        if executor is None:
            def executor(clone, fn, args):
                return Venue(clone.spec).execute(fn, *args)
        self.executor = executor
        self.busy_energy_j = 0.0
        self.tokens_emitted = 0
        self.ledger = SlotLedger()
        self.kv_samples: List[tuple] = []   # (written_tokens, reserved)
        self._kv_pools: Dict[int, KVBlockPool] = {}   # clone.cid -> pool
        # prefix-cache / preemption economics (ADR-003)
        self.preemptions = 0
        self.restored_tokens = 0
        self.prefix_hit_tokens = 0
        self.steps_built = 0      # engine steps built; a step's trace id
        self.prompt_tokens = 0
        # fleet economics (ADR-004)
        self.energy_j_by_type: Dict[str, float] = {}
        self.busy_seconds_by_type: Dict[str, float] = {}
        self.fleet_mix: Dict[str, int] = {}        # completions per type
        self._escalated: set = set()               # rids forced up a tier
        # peak queued demand per (tenant, priority, required tier) class
        self.demand_by_class: Dict[tuple, int] = {}
        # rid -> (lo, hi) placement band, valid for one scheduler round
        # (invalidated whenever pool inventory changes — engine spawns)
        self._band_cache: Dict[int, tuple] = {}
        # SLO-aware gateway (ADR-007): arrivals flow through it when
        # present; it shares the serving timeline and link profile
        self.gateway = gateway
        if gateway is not None:
            gateway.adopt_clock(self.clock)
        # fault tolerance + hedging (ADR-006); a gateway hears about
        # kills/drains at the fault instant (capacity-loss signal)
        self.injector = (FaultInjector(
            self.pool, faults,
            on_fire=(gateway.note_fault if gateway is not None else None))
            if faults else None)
        self._peak_queue_depth = 0
        self.hedge_factor = hedge_factor
        self.hedge_quantile = hedge_quantile
        self.hedge_min_samples = hedge_min_samples
        self.recoveries_migrated = 0
        self.recoveries_restored = 0
        self.hedges_fired = 0
        self.hedge_wins = 0
        self._hedges: Dict[object, object] = {}   # task <-> partner
        self._step_hist: List[float] = []         # recent step durations
        self._kv_tok_bytes: Optional[float] = None
        # speculative telemetry (ADR-008); ``spec_draft_cids`` records
        # every clone ever paired as a draft (fault tests target them),
        # ``_spec_rng`` drives the deterministic bench-harness corruption
        self.spec_rounds = 0
        self.spec_tokens = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_fallbacks = 0
        self.spec_draft_cids: List[int] = []
        self._spec_rng = np.random.default_rng(0xC0FFEE)
        # disaggregated prefill + routing state (ADR-009): the partner
        # tier defaults to the top of the fleet ladder (prefill is
        # compute-bound — the fastest tier amortizes best).  ONE partner
        # clone is shared, refcounted, by every disagg engine — that
        # sharing is the $-economics of the whole design: k cheap decode
        # engines amortize a single premium prefill clone.  Each engine
        # still owns a private partner-side scratch pool (keyed by its
        # *decode* clone, reused across engine generations), so
        # overlapping partner dispatches never clobber device state.
        # The seeded routing rng keeps the "random" arm deterministic.
        if disagg_prefill_type is not None \
                and disagg_prefill_type not in CLONE_TYPES:
            raise ValueError(f"unknown disagg_prefill_type "
                             f"{disagg_prefill_type!r}")
        self.disagg_prefill_type = disagg_prefill_type or self.fleet[-1]
        self._prefill_pools: Dict[int, KVBlockPool] = {}
        self._partner_clone = None
        self._partner_refs = 0
        self._route_rng = np.random.default_rng(0xD15A66)
        self.disagg_handoffs = 0
        self.disagg_colocated = 0
        self.disagg_fallbacks = 0
        self.kv_transfer_bytes = 0.0
        self.kv_transfer_s = 0.0
        self.per_clone_stats: Dict[int, Dict[str, object]] = {}
        self._disagg_blk_bytes: Optional[int] = None
        self._n_params: Optional[int] = None

    # ---------------------------------------------------------------- clones
    def _free_clone(self, lo_rank: Optional[int] = None,
                    hi_rank: Optional[int] = None,
                    prefer_cid: Optional[int] = None):
        """Best usable clone inside the ``[lo_rank, hi_rank]`` band:
        soonest-ready first (a free clone must never lose to one still
        booting), then the smallest tier, then cid.  Cost discipline
        lives in the band itself — a request's ``hi`` is the tier the
        placement policy chose for it, so a dearer tier is simply not a
        candidate.  The primary is exempt from the band's *upper* bound:
        it is standing capacity billed whether or not it serves, so using
        it can never squat paid-for premium.

        ``prefer_cid`` (ADR-009 affinity routing) wins among candidates
        tied on readiness — a prefix-warm clone beats tier order, but a
        free clone still never loses to one that is booting.  Under
        ``routing="random"`` the pick is uniform over the candidate set
        (the affinity sweep's control arm)."""
        def in_band(rank, primary=False):
            return ((lo_rank is None or rank >= lo_rank)
                    and (primary or hi_rank is None or rank <= hi_rank))

        now = self.clock.now()
        cands = []
        p = self.pool.primary
        # dead / open-breaker clones never take new work (ADR-006): a
        # tripped breaker re-closes only through its half-open probe
        if self.use_primary and not p.busy and p.serveable \
                and in_band(p.ctype.rank(), primary=True):
            cands.append((0.0, p.ctype.rank(), 0, p))
        for c in self.pool.running_secondaries():
            if c.busy or not c.serveable \
                    or c.ctype.name not in self._fleet_set:
                continue
            if not in_band(c.ctype.rank()):
                continue
            cands.append((self.autoscaler.clone_ready_delay(c, now),
                          c.ctype.rank(), c.cid, c))
        if not cands:
            return None
        if self.routing == "random":
            return cands[int(self._route_rng.integers(len(cands)))][3]
        best = min(cands)
        if prefer_cid is not None:
            for c in cands:
                if c[3].cid == prefer_cid and c[0] <= best[0] + 1e-12:
                    return c[3]
        return best[3]

    def _net_s(self, nbytes: int) -> float:
        return transfer_time(nbytes, self.pool.link)

    def _kv_token_bytes(self) -> float:
        """Bytes of KV state one context token occupies — what a block
        migration or a hedge's context transfer moves per token.  Derived
        from the backend's own cache accounting when it has one (test
        stubs fall back to a small constant)."""
        if self._kv_tok_bytes is None:
            fn = getattr(self.backend, "cache_mem_bytes", None)
            if fn is not None:
                self._kv_tok_bytes = float(fn(1)) / self.backend.capacity
            else:
                self._kv_tok_bytes = 64.0
        return self._kv_tok_bytes

    # ------------------------------------------- disagg / affinity (ADR-009)
    def _clone_stat(self, clone) -> Dict[str, object]:
        """Per-clone routing telemetry bucket (ServeReport.per_clone)."""
        st = self.per_clone_stats.get(clone.cid)
        if st is None:
            st = self.per_clone_stats[clone.cid] = {
                "type": clone.ctype.name, "prefix_hit_tokens": 0,
                "prompt_tokens": 0, "kv_transfer_bytes": 0.0,
                "kv_transfer_s": 0.0}
        return st

    def _disagg_block_bytes(self) -> int:
        """Modeled wire bytes of one KV block on the handoff link —
        ``venues.kv_block_bytes`` when the backend carries a real model
        config (int8 payload + per-head scales when compressing), else a
        backend-derived fallback (test stubs)."""
        if self._disagg_blk_bytes is None:
            cfg = getattr(self.backend, "cfg", None)
            if cfg is not None and hasattr(cfg, "layer_kinds"):
                self._disagg_blk_bytes = kv_block_bytes(
                    cfg, self.block_size, quantized=self.disagg_compress)
            else:
                raw = self._kv_token_bytes() * self.block_size
                self._disagg_blk_bytes = int(
                    raw / 4 if self.disagg_compress else raw)
        return self._disagg_blk_bytes

    def _param_count(self) -> Optional[int]:
        """Backend parameter count (prefill FLOPs model); None for stub
        backends whose params aren't an array pytree."""
        if self._n_params is None:
            try:
                self._n_params = sum(
                    int(np.prod(x.shape))
                    for x in jax.tree.leaves(self.backend.params))
            except Exception:
                self._n_params = -1
        return None if self._n_params < 0 else self._n_params

    def _disagg_worth(self, engine: "_SlotEngine", plen: int) -> bool:
        """Per-request disagg-vs-co-located planner: ship the prefill to
        the partner tier only when the modeled prefill-time gain (prompt
        FLOPs at the decode tier vs the partner tier) exceeds the KV
        handoff's wire cost on ``disagg_link``.  An explicit
        ``disagg_min_prompt`` replaces the model with a plain length
        threshold (and is the stub-backend fallback)."""
        if self.disagg_min_prompt is not None:
            return plen >= self.disagg_min_prompt
        pc = self._param_count()
        if pc is None:
            return True
        flops = 2.0 * pc * plen
        gain = (flops / engine.clone.spec.eff_flops
                - flops / engine.prefill_clone.spec.eff_flops)
        nb = -(-plen // self.block_size)
        wire = transfer_time(nb * self._disagg_block_bytes(),
                             LINKS[self.disagg_link])
        return gain > wire

    def _affinity_depth(self, kvp: KVBlockPool, req: ServeRequest) -> int:
        """Cached-prefix depth (tokens) this pool holds for ``req`` — the
        affinity routing score; pure (``match_prefix`` mutates nothing)."""
        eff = _SlotEngine.effective_prompt(req, self.prompt_pad,
                                           self.backend.capacity)
        return int(kvp.match_prefix(eff)[2])

    def _affinity_by_type(self, req: ServeRequest) -> Dict[str, int]:
        """Per-tier best prefix-match depth over live clone pools — the
        ``prefix_affinity`` hint's input to PlacementEngine.choose_type."""
        out: Dict[str, int] = {}
        by_cid = {c.cid: c for c in self.pool.clones}
        for cid, kvp in self._kv_pools.items():
            clone = by_cid.get(cid)
            if clone is None or not clone.serveable:
                continue
            d = self._affinity_depth(kvp, req)
            t = clone.ctype.name
            out[t] = max(out.get(t, 0), d)
        return out

    def _best_affinity_cid(self, req: ServeRequest) -> Optional[int]:
        """Clone id with the deepest cached prefix for ``req`` (spawn-
        time affinity: engine pools persist across generations on the
        same clone, so routing the spawn there revives its index)."""
        best, best_d = None, 0
        for cid, kvp in sorted(self._kv_pools.items()):
            d = self._affinity_depth(kvp, req)
            if d > best_d:
                best, best_d = cid, d
        return best

    # ------------------------------------------------------------- placement
    def _charge(self, clone, venue_seconds: float) -> None:
        """Bill one dispatch's busy energy, chips-aware (ADR-004): the
        venue's chip count scales the bill through the TPU energy model
        instead of the old flat ``venue_seconds x power_peak``."""
        e = self.energy.busy_j(chips=clone.spec.chips, seconds=venue_seconds)
        self.busy_energy_j += e
        t = clone.ctype.name
        self.energy_j_by_type[t] = self.energy_j_by_type.get(t, 0.0) + e
        self.busy_seconds_by_type[t] = (
            self.busy_seconds_by_type.get(t, 0.0) + venue_seconds)

    def _blocks_for_type(self, type_name: str) -> int:
        """KV block-pool size for an engine on this clone type: the base
        tier gets exactly ``num_blocks``, bigger tiers scale with the
        fleet memory ladder (``KV_SCALE_BY_CLONE_TYPE``), all capped at
        the worst case every slot could ever write."""
        max_blk = -(-self.backend.capacity // self.block_size)
        worst = self.max_batch * max_blk + 1
        if self.num_blocks is None:
            return worst
        if len(self.fleet) == 1:     # homogeneous: exact pre-fleet sizing
            return self.num_blocks
        scale = (KV_SCALE_BY_CLONE_TYPE[type_name]
                 / KV_SCALE_BY_CLONE_TYPE[self.clone_type])
        return min(worst, max(2, int(self.num_blocks * scale)))

    def _request_blocks(self, req: ServeRequest) -> int:
        """Worst-case KV blocks this request's slot can come to hold
        (prompt+window demand — mirrors ``KVBlockPool._need_blocks`` over
        the effective restore-aware prompt length)."""
        p = self.prompt_pad
        if req.generated:
            p = min(p + len(req.generated) - 1, self.backend.capacity)
        total = min(p + req.max_new_tokens, self.backend.capacity)
        max_blk = -(-self.backend.capacity // self.block_size)
        return min(-(-max(total, p) // self.block_size), max_blk)

    def _required_type(self, req: ServeRequest) -> str:
        """The smallest fleet tier whose block pool holds this request —
        the live admission analogue of the paper's OutOfMemoryError ->
        bigger-VM escalation.  Homogeneous fleets (and the contiguous
        cohort path, which has no block pool) short-circuit to the base
        type; a request no tier can hold degrades to the top tier, where
        preemption absorbs the squeeze."""
        if len(self.fleet) == 1 or self.kv_mode != "paged":
            return self.clone_type
        t = self.placement.required_type(
            self.clone_type, self._request_blocks(req),
            lambda n: self._blocks_for_type(n) - 1)   # -1: trash block
        if t != self.clone_type:
            self._escalated.add(req.rid)
        return t

    def _placement_band(self, req: ServeRequest) -> tuple:
        """Rank band ``(lo, hi)`` of clone types this request may run on.

        ``lo`` is the escalation floor (KV demand); ``hi`` the tier the
        placement engine would provision for it *now* — so bulk does not
        squat on premium engines' free slots just because their rank is
        adequate, and the fleet's $-policy governs joins as well as
        spawns.  Urgent requests rank by latency, so their band widens to
        whatever tier is warm.  Homogeneous fleets are unconstrained
        (``(None, None)`` — exact pre-fleet behaviour: the single
        secondary type plus the always-on primary).  Bands are cached per
        scheduler round (``_band_cache``) — they depend only on the
        request and pool inventory, not on which engine asks."""
        cached = self._band_cache.get(req.rid)
        if cached is not None:
            return cached
        if len(self.fleet) == 1:
            band = (None, None)
        else:
            rt = self._required_type(req)
            lo = CLONE_TYPES[rt].rank()
            hints = {}
            if self.routing == "affinity":
                # prefix-affinity placement (ADR-009): a tier holding the
                # request's cached prefix outranks the $-policy order
                hints = {"hint": "prefix_affinity",
                         "affinity": self._affinity_by_type(req)}
            ct = self.placement.choose_type(rt, urgent=req.priority > 0,
                                            **hints) or rt
            band = (lo, max(lo, CLONE_TYPES[ct].rank()))
        self._band_cache[req.rid] = band
        return band

    def _in_band(self, req: ServeRequest, clone) -> bool:
        """Is ``clone`` inside the request's placement band?  The primary
        is exempt from the upper bound (standing capacity — see
        ``_free_clone``)."""
        lo, hi = self._placement_band(req)
        rank = clone.ctype.rank()
        if lo is not None and rank < lo:
            return False
        return clone.is_primary or hi is None or rank <= hi

    def _fits_slot(self, engine: "_SlotEngine", req: ServeRequest) -> bool:
        """May ``req`` take a free slot of this engine right now?  Tier
        must sit in the request's placement band and the engine's block
        pool must admit the effective prompt (prefix matching applies)."""
        return (self._in_band(req, engine.clone)
                and engine.kv.can_admit(
                    _SlotEngine.effective_prompt(
                        req, self.prompt_pad, self.backend.capacity),
                    req.max_new_tokens))

    def _demand_buckets(self) -> List[tuple]:
        """Queued demand as ``(required_type, urgent, cohort_units)``
        buckets for the autoscaler, tracked per tenant/priority class and
        per KV-footprint tier (``demand_by_class`` keeps the per-class
        peaks for telemetry)."""
        counts: Dict[tuple, int] = {}
        for r in self.queue.snapshot():
            key = (self._required_type(r), r.priority > 0, r.tenant)
            counts[key] = counts.get(key, 0) + 1
        agg: Dict[tuple, int] = {}
        for (t, urgent, _tenant), n in counts.items():
            agg[(t, urgent)] = agg.get((t, urgent), 0) + n
        for key, n in counts.items():
            self.demand_by_class[key] = max(self.demand_by_class.get(key, 0),
                                            n)
        return [(t, urgent, -(-n // self.max_batch))
                for (t, urgent), n in agg.items()]

    @staticmethod
    def _in_flight_by_type(inflight: Dict) -> Dict[str, int]:
        """In-flight work units per clone type (engines and cohorts)."""
        out: Dict[str, int] = {}
        for unit in inflight.values():
            t = unit.clone.ctype.name
            out[t] = out.get(t, 0) + 1
        return out

    # ---------------------------------------------------------------- cohort
    def _start_cohort(self, batch: List[ServeRequest], clone):
        plen = self.prompt_pad
        toks = np.zeros((len(batch), plen), np.int32)
        for i, r in enumerate(batch):
            toks[i, :min(len(r.prompt), plen)] = r.prompt[:plen]
        cohort = _Cohort(reqs=batch, clone=clone, plen=plen,
                         outs=[[] for _ in batch],
                         first_token_t=[0.0] * len(batch),
                         token_ts=[[] for _ in batch])
        clone.busy = True
        delay = (self.autoscaler.clone_ready_delay(clone, self.clock.now())
                 + self._net_s(toks.nbytes))
        task = self.dispatcher.submit(
            clone, self.backend.prefill, (self.backend.params,
                                          jnp.asarray(toks)),
            executor=self.executor, extra_delay=delay, label="prefill")
        self._charge(clone, task.venue_seconds)
        return task, cohort

    def _submit_decode(self, cohort: _Cohort):
        pos = jnp.int32(min(cohort.plen + cohort.step,
                            self.backend.capacity - 1))
        written = len(cohort.reqs) * min(cohort.plen + cohort.step + 1,
                                         self.backend.capacity)
        self.kv_samples.append((written,
                                len(cohort.reqs) * self.backend.capacity))
        task = self.dispatcher.submit(
            cohort.clone, self.backend.decode,
            (self.backend.params, cohort.cache, cohort.tok, pos),
            executor=self.executor,
            extra_delay=self._net_s(len(cohort.reqs) * 8), label="decode")
        self._charge(cohort.clone, task.venue_seconds)
        return task

    def _retire(self, cohort: _Cohort, completions: List[ServeCompletion]
                ) -> bool:
        """Emit current tokens; drop finished rows.  True while alive."""
        now = self.clock.now()
        tok = np.asarray(cohort.tok)[:, 0]
        keep = []
        for i, r in enumerate(cohort.reqs):
            cohort.outs[i].append(int(tok[i]))
            cohort.token_ts[i].append(now)
            if len(cohort.outs[i]) == 1:
                cohort.first_token_t[i] = now
            if len(cohort.outs[i]) >= r.max_new_tokens:
                self.tokens_emitted += len(cohort.outs[i])
                completions.append(ServeCompletion(
                    r.rid, cohort.outs[i], r.arrival_t,
                    cohort.first_token_t[i], now, cohort.clone.spec.name,
                    tenant=r.tenant, slo=r.slo, deadline_s=r.deadline_s,
                    token_ts=cohort.token_ts[i]))
                t = cohort.clone.ctype.name
                self.fleet_mix[t] = self.fleet_mix.get(t, 0) + 1
            else:
                keep.append(i)
        if not keep:
            self.pool.release([cohort.clone])
            return False
        if len(keep) < len(cohort.reqs):      # leave at step granularity
            cohort.reqs = [cohort.reqs[i] for i in keep]
            cohort.outs = [cohort.outs[i] for i in keep]
            cohort.first_token_t = [cohort.first_token_t[i] for i in keep]
            cohort.token_ts = [cohort.token_ts[i] for i in keep]
            cohort.tok = cohort.tok[np.asarray(keep, np.int32)]
            cohort.cache = self.backend.cache_take(cohort.cache, keep)
        return True

    # ------------------------------------------------------------- slots
    def _start_engine(self, clone) -> _SlotEngine:
        """Engine for ``clone``; the clone's KV pool is allocated once and
        reused (reset) across engine generations — no per-spawn zeros, and
        the prefix index survives, so cached prompts keep paying off.

        A speculative handler (ADR-008) additionally pairs the engine
        with a *draft* clone on the cheapest adequate tier (the
        ``spec_draft`` placement hint) and gives it a fresh draft-model
        pool with the target pool's exact block geometry — the two sides
        share one set of block tables.  No draft clone available means
        the engine simply runs non-speculative (never a stall)."""
        clone.busy = True
        kv = self._kv_pools.get(clone.cid)
        if kv is None:
            kv = KVBlockPool(self.backend, self.max_batch, self.block_size,
                             self._blocks_for_type(clone.ctype.name),
                             prefix_cache=self.prefix_cache)
            self._kv_pools[clone.cid] = kv
        else:
            kv.reset()
        engine = _SlotEngine(self.backend, clone, kv, self.decode_window,
                             self.donate_kv, self.prefill_chunk,
                             self.mixed_dispatch)
        if self.speculative:
            dc = self._acquire_draft_clone(clone)
            if dc is not None:
                engine.spec_on = True
                engine.draft_clone = dc
                engine.spec_k = self.spec_k
                engine.draft_pool = self.backend.init_draft_pool(
                    kv.max_slots, kv.num_blocks, kv.bs)
                if dc.cid not in self.spec_draft_cids:
                    self.spec_draft_cids.append(dc.cid)
            else:
                self.spec_fallbacks += 1
        if self.disagg:
            pc = self._acquire_prefill_clone(clone)
            if pc is not None:
                engine.disagg_on = True
                engine.prefill_clone = pc
                ppool = self._prefill_pools.get(clone.cid)
                if ppool is None:
                    # scratch pool: worst-case blocks (it holds at most
                    # max_batch in-flight prompts), no prefix index — the
                    # partner's content is transient by design
                    ppool = KVBlockPool(self.backend, self.max_batch,
                                        self.block_size, None,
                                        prefix_cache=False)
                    self._prefill_pools[clone.cid] = ppool
                else:
                    ppool.reset()
                engine.prefill_pool = ppool
            else:
                self.disagg_fallbacks += 1
        return engine

    def _acquire_prefill_clone(self, decode_clone):
        """Attach the engine to the SHARED disagg partner clone
        (ADR-009), refcounted: the first engine claims a clone of the
        prefill tier — a free RUNNING clone preferred, else one resumed/
        booted through the pool lifecycle — and later engines just bump
        the refcount.  The decode clone itself is never a candidate.
        None degrades the engine to co-located prefill — never a
        stall."""
        if (self._partner_clone is not None
                and self._partner_clone.serveable):
            self._partner_refs += 1
            return self._partner_clone
        t = self.disagg_prefill_type
        for c in self.pool.running_secondaries():
            if (c is not decode_clone and not c.busy and c.serveable
                    and c.ctype.name == t):
                c.busy = True
                self._partner_clone, self._partner_refs = c, 1
                return c
        try:
            clones, _ = self.pool.acquire(t, n=1, exclude_primary=True)
        except ClonePoolExhausted:
            return None
        for c in clones:
            if c is not decode_clone and c.serveable:
                c.busy = True
                self._partner_clone, self._partner_refs = c, 1
                return c
        self.pool.release(clones)
        return None

    def _release_partner(self) -> None:
        """Drop one engine's reference on the shared partner clone; the
        clone returns to the pool (idle-TTL pause/power-off applies) when
        the last disagg engine lets go."""
        self._partner_refs = max(0, self._partner_refs - 1)
        if self._partner_refs == 0 and self._partner_clone is not None:
            self.pool.release([self._partner_clone])
            self._partner_clone = None

    def _acquire_draft_clone(self, verify_clone):
        """Claim a cheap-tier clone as the engine's draft partner.  The
        placement hint picks the cheapest $-rate tier the fleet offers;
        a free RUNNING clone of that tier is preferred, else one is
        resumed/booted through the pool lifecycle.  The verify clone
        itself is never a candidate (the whole point is overlap)."""
        t = self.placement.choose_type(self.fleet[0], hint="spec_draft") \
            or self.fleet[0]
        for c in self.pool.running_secondaries():
            if (c is not verify_clone and not c.busy and c.serveable
                    and c.ctype.name == t):
                c.busy = True
                return c
        try:
            clones, _ = self.pool.acquire(t, n=1, exclude_primary=True)
        except ClonePoolExhausted:
            return None
        for c in clones:
            if c is not verify_clone and c.serveable:
                c.busy = True
                return c
        self.pool.release(clones)
        return None

    def _release_engine(self, engine: _SlotEngine) -> None:
        """Return an engine's clone — and its draft partner — to the
        pool."""
        clones = [engine.clone]
        if engine.draft_clone is not None:
            clones.append(engine.draft_clone)
            engine.draft_clone = None
            engine.spec_on = False
        if engine.prefill_clone is not None:
            engine.prefill_clone = None
            engine.disagg_on = False
            self._release_partner()
        self.pool.release(clones)

    def _admit(self, engine: _SlotEngine, req: ServeRequest) -> None:
        """Admit through the engine, folding the admission's prefix-cache
        economics into the handler's report counters.  Disagg-eligible
        cold prompts are intercepted first (ADR-009): they allocate a
        decode-side slot but defer the prefill to the engine's partner
        clone.  Stamps ``req.slot_t`` the first time the request takes a
        slot (the ``serve.admit`` span)."""
        if req.slot_t is None:
            req.slot_t = self.clock.now()
        with span("serve.admit", rid=req.rid) as sp:
            st = self._clone_stat(engine.clone)
            if self._try_disagg_admit(engine, req):
                plen = self.prompt_pad      # fresh eff is exactly pad-long
                self.prompt_tokens += plen
                st["prompt_tokens"] += plen
                sp.set_metadata(cached=0, prompt=plen)
                return
            info = engine.admit(req, self.prompt_pad)
            self.prefix_hit_tokens += info["cached"]
            self.prompt_tokens += info["prompt"]
            st["prefix_hit_tokens"] += info["cached"]
            st["prompt_tokens"] += info["prompt"]
            if info["restore"]:
                self.restored_tokens += info["suffix"]
            sp.set_metadata(cached=info["cached"], prompt=info["prompt"])

    def _try_disagg_admit(self, engine: _SlotEngine,
                          req: ServeRequest) -> bool:
        """Route a cold prompt to the disaggregated prefill path when the
        transfer-cost planner says the partner's compute win beats the
        KV wire cost (ADR-009).  Local prefix hits always stay
        co-located — reusing resident blocks is strictly cheaper than
        recomputing the prefix remotely and shipping it back."""
        if not (engine.disagg_on and engine.prefill_clone is not None):
            return False
        if req.generated:          # restore path: suffix scan is local
            return False
        eff = _SlotEngine.effective_prompt(req, self.prompt_pad,
                                           engine.kv.capacity)
        if engine.kv.match_prefix(eff)[2] > 0:
            return False
        if not self._disagg_worth(engine, len(eff)):
            self.disagg_colocated += 1
            return False
        slot, new_ids, _, _ = engine.kv.alloc_slot(
            eff, req.max_new_tokens, force_suffix=True)
        ids = [int(b) for b in new_ids]
        engine.disagg_joins.append((slot, req, eff, ids))
        engine.disagg_blocks[slot] = ids
        return True

    def _preempt_slot(self, engine: _SlotEngine, victim: int,
                      counts: np.ndarray) -> None:
        """Evict ``victim`` under pool pressure: carry its generated
        tokens and TTFT stamp on the request, reclaim its blocks (shared
        prompt blocks stay resident in the prefix index, so the restore
        is prefix-accelerated), and requeue it at the queue head."""
        s = engine.slots[victim]
        req = s.req
        req.generated = list(s.out)
        req.first_token_t = s.first_token_t
        req.token_ts = list(s.token_ts)
        req.preemptions += 1
        engine.slots[victim] = None
        engine.tok_host[victim] = 0
        counts[victim] = 0
        engine.kv.free_slot(victim)
        self.queue.requeue(req)
        self.preemptions += 1

    def _cancel_join(self, engine: _SlotEngine) -> None:
        """Roll back the newest not-yet-submitted join under pool
        pressure: its prefill never ran, so nothing is lost — the slot
        and blocks return to the pool and the request requeues at the
        head.  Always preferred over preempting an *active* slot, whose
        restore re-computes real work.  Pending inbound migrations are
        rolled back last-resort-before-preemption: the device copy never
        ran, so the request downgrades to the restore recovery path with
        its generated tokens carried (ADR-006)."""
        if engine.sfx_joins:
            slot, req, _, _, _ = engine.sfx_joins.pop()
        elif engine.joins:
            slot, req, _, _ = engine.joins.pop()
        elif engine.disagg_joins:
            # partner prefill not yet submitted: free rollback too
            slot, req, _, _ = engine.disagg_joins.pop()
            engine.disagg_blocks.pop(slot, None)
            engine.kv.cancel_slot(slot)
            self.queue.requeue(req)
            self.preemptions += 1
            return
        else:
            m = engine.migrations.pop()
            slot, req, out, ft = m[0], m[1], m[2], m[3]
            kind = m[9] if len(m) > 9 else "recover"
            req.generated = list(out)
            req.first_token_t = ft
            req.preemptions += 1
            if kind == "disagg":
                # prompt blocks were suffix-indexed at admit; the partner
                # slot holding the computed KV is dropped with the copy
                engine.disagg_blocks.pop(slot, None)
                engine.kv.cancel_slot(slot)
                if engine.prefill_pool is not None:
                    engine.prefill_pool.free_slot(m[7])
            else:
                engine.kv.free_slot(slot)   # int-admitted: nothing indexed
                self.recoveries_restored += 1
            self.queue.requeue(req)
            self.preemptions += 1
            return
        engine.cow_pairs = [p for p in engine.cow_pairs if p[0] != slot]
        engine.kv.cancel_slot(slot)
        self.queue.requeue(req)
        self.preemptions += 1

    def _grow_or_preempt(self, engine: _SlotEngine,
                         counts: np.ndarray) -> None:
        """Reserve the window's blocks, shedding load on exhaustion — the
        replacement for the old hard ``RuntimeError``: first roll back
        pending joins (free), then preempt active victims (restorable).
        Each retry frees one slot's private blocks; the loop terminates
        because either growth succeeds or the engine runs out of victims
        (a single slot whose context cannot fit the pool is
        unservable)."""
        kv = engine.kv
        while True:
            try:
                kv.grow_for_window(counts)
                return
            except PoolExhausted:
                if (engine.joins or engine.sfx_joins or engine.migrations
                        or engine.disagg_joins):
                    self._cancel_join(engine)
                    continue
                cands = [(slot, s.req.priority, len(s.out))
                         for slot, s in enumerate(engine.slots)
                         if s is not None and kv.active[slot]]
                if len(cands) <= 1:
                    raise RuntimeError(
                        "KV block pool cannot hold a single request's "
                        f"context (num_blocks={kv.num_blocks}, "
                        f"block_size={kv.bs}): preemption has no victim "
                        "left — raise num_blocks or lower capacity")
                # prefer victims whose restore context fits capacity: a
                # slot decoding past capacity keeps overwriting the last
                # cell, an overwrite history a re-prefill cannot replay,
                # so evicting one forfeits restore token-identity — only
                # done when no in-capacity victim remains
                safe = [c for c in cands
                        if self.prompt_pad + c[2] - 1
                        <= self.backend.capacity]
                self._preempt_slot(
                    engine, self.ledger.pick_victim(safe or cands),
                    counts)

    def _submit_engine_step(self, engine: _SlotEngine):
        """Build the engine's next step (the ``serve.step.build`` span,
        numbered by ``steps_built``) and dispatch it; returns the task."""
        self.steps_built += 1
        engine.step_id = self.steps_built
        with span("serve.step.build", step=engine.step_id) as sp:
            submit = self._build_engine_step(engine, sp)
        return submit()

    def _build_engine_step(self, engine: _SlotEngine, sp) -> Callable:
        """One dispatched unit of engine work: fold every pending join's
        prefill into the step — full batched prefill for cold prompts,
        device block copies for CoW splits, a suffix scan for prefix-hit
        and restored rows — then decode a multi-token *window* for all
        previously-active slots (one device dispatch for up to
        ``decode_window`` tokens per slot; rows at their budget park
        mid-window writes in the trash block).  In-closure order matters:
        full prefills write the blocks the same round's CoW copies read,
        and both precede the suffix scans that attend over them.

        The dispatched closure is *pure* over its bound arguments (the
        Venue executor re-runs it to stabilize timing), so all block/slot
        bookkeeping — including preemption — happens here on the host
        before submission.  Returns the call that submits the step; the
        step's counts go on ``sp`` (the build span) and on the closure's
        ``trace_meta`` (the dispatch span).
        """
        kv = engine.kv
        spec = engine.spec_on and engine.draft_clone is not None
        # tokens each slot will emit this window: min(window, budget left).
        # A speculative round sizes each row's window as its *verify*
        # width k_i + 1 instead (ADR-008): k_i adapts to the row's draft
        # acceptance EMA, clamped so (a) at least the current token is
        # scored, (b) the budget can absorb a full accept (k <= left - 1),
        # and (c) no window write ever needs the capacity - 1 pin
        # (k <= capacity - 1 - pos — a pinned write would collapse
        # last-live-wins and break stepwise token identity).
        counts = np.zeros((kv.max_slots,), np.int32)
        for slot in np.nonzero(kv.active)[0]:
            s = engine.slots[slot]
            left = s.req.max_new_tokens - len(s.out)
            if spec:
                p = int(kv.pos[slot])
                room = max(kv.capacity - 1 - min(p, kv.capacity - 1), 0)
                k = max(1, int(round(s.req.spec_ema * engine.spec_k)))
                k = max(min(k, engine.spec_k, left - 1, room), 0)
                counts[slot] = k + 1
            else:
                counts[slot] = min(engine.window, left)
        if counts.any():
            # whole window's blocks up front; exhaustion rolls back
            # pending joins / preempts victims (zeroing their counts)
            # instead of raising — must run before the join lists are
            # taken, so rollback can still un-admit them
            self._grow_or_preempt(engine, counts)
        joins, engine.joins = engine.joins, []
        sfx, engine.sfx_joins = engine.sfx_joins, []
        cow, engine.cow_pairs = engine.cow_pairs, []
        migs, engine.migrations = engine.migrations, []
        engine.submitted_joins = joins
        engine.submitted_sfx = sfx
        engine.submitted_migrations = migs
        sp.set_metadata(joins=len(joins), sfx=len(sfx), cow=len(cow))
        rows = np.nonzero(kv.active)[0]
        do_decode = rows.size > 0
        engine.decode_rows = rows if do_decode else None
        if do_decode:
            # written-token sample: writes past capacity pin to the last
            # cell (same clamp the host fold applies to kv.pos), so they
            # must not count as newly written either
            eff = np.minimum(counts, np.maximum(kv.capacity - kv.pos, 0))
            written = kv.written_tokens() + int(eff.sum())
            self.kv_samples.append((written, kv.used_blocks() * kv.bs))
        engine.decode_counts = counts
        tables = engine.device_tables()      # re-uploaded only when dirty
        pos = jnp.asarray(np.minimum(kv.pos, self.backend.capacity - 1))
        tok = jnp.asarray(engine.tok_host[:, None])
        steps_left = jnp.asarray(counts)
        prefill_into = engine.prefill_into
        decode_window = engine.decode_window
        nbytes = 8 * int(counts.sum())
        join_batch = None
        if joins:
            # joins landing at the same boundary prefill as ONE batched
            # call, padded to a power-of-two bucket so the prefill only
            # ever compiles for log2(max_batch) join counts.  Pad rows
            # scatter nowhere: slot id ``max_slots`` is out of range
            # (state-row update dropped) and block id 0 is the trash block.
            j = len(joins)
            jpad = pow2_bucket(j)
            toks = jnp.concatenate(
                [t for _, _, t, _ in joins]
                + [jnp.zeros((jpad - j,) + joins[0][2].shape[1:],
                             jnp.int32)] * (jpad > j), axis=0)
            blks = jnp.concatenate(
                [jnp.stack([b for _, _, _, b in joins])]
                + [jnp.zeros((jpad - j, joins[0][3].shape[0]),
                             jnp.int32)] * (jpad > j), axis=0)
            slots = jnp.asarray([s for s, _, _, _ in joins]
                                + [kv.max_slots] * (jpad - j), jnp.int32)
            join_batch = (toks, blks, slots)
            nbytes += int(toks.nbytes)
        cow_batch = None
        if cow:
            # CoW splits as one fused device copy; (0, 0) pads are no-ops
            cpad = pow2_bucket(len(cow))
            src = jnp.asarray([s for _, s, _ in cow]
                              + [0] * (cpad - len(cow)), jnp.int32)
            dst = jnp.asarray([d for _, _, d in cow]
                              + [0] * (cpad - len(cow)), jnp.int32)
            cow_batch = (self.backend.copy_fn(self.donate_kv), src, dst)
            nbytes += int(src.nbytes) * 2
        mig_batches = []
        xfer_s = 0.0
        if migs:
            # inbound KV migrations: one fused cross-pool copy per
            # (source pool, kind) — block ids padded to a power-of-two
            # bucket with (0, 0) trash-to-trash no-ops, destination
            # state-row pads dropped via an out-of-range slot id.  The
            # *real* KV bytes cross the inter-clone link: recovery moves
            # (ADR-006) bill into nbytes on the generic net model, while
            # disagg handoffs (ADR-009) bill per *block* on the
            # configured LinkProfile — optionally int8-compressed in
            # flight, which both shrinks the modeled bytes ~4x and
            # round-trips the payload through the real quantize /
            # dequantize device ops.
            by_src: Dict[tuple, list] = {}
            for m in migs:
                kind = m[9] if len(m) > 9 else "recover"
                by_src.setdefault((id(m[4]), kind), []).append(m)
            for (_, kind), group in by_src.items():
                src_pool = group[0][4]
                sids = [b for m in group for b in m[5]]
                dids = [b for m in group for b in m[6]]
                n_blk = len(sids)
                bpad = pow2_bucket(n_blk)
                sids += [0] * (bpad - n_blk)
                dids += [0] * (bpad - n_blk)
                spad = pow2_bucket(len(group))
                sslots = [m[7] for m in group] + [0] * (spad - len(group))
                dslots = [m[0] for m in group] \
                    + [kv.max_slots] * (spad - len(group))
                compress = kind == "disagg" and self.disagg_compress
                # positional arg only when compressing: stub backends
                # (tests) expose the legacy zero-arg migrate_fn
                mfn = (self.backend.migrate_fn(True) if compress
                       else self.backend.migrate_fn())
                mig_batches.append(
                    (mfn, src_pool,
                     jnp.asarray(sids, jnp.int32),
                     jnp.asarray(dids, jnp.int32),
                     jnp.asarray(sslots, jnp.int32),
                     jnp.asarray(dslots, jnp.int32)))
                if kind == "disagg":
                    dbytes = n_blk * self._disagg_block_bytes()
                    dt = transfer_time(dbytes, LINKS[self.disagg_link])
                    xfer_s += dt
                    self.kv_transfer_bytes += dbytes
                    self.kv_transfer_s += dt
                    st = self._clone_stat(engine.clone)
                    st["kv_transfer_bytes"] += dbytes
                    st["kv_transfer_s"] += dt
                else:
                    nbytes += int(sum(m[8] for m in group)
                                  * self._kv_token_bytes())
        sfx_batch = None
        mixed_batch = None
        sfx_steps = 0
        mix_steps = 0
        if sfx:
            # prefix-hit / restore rows: suffix-only prefill as ONE
            # teacher-forced scan, rows and steps padded to power-of-two
            # buckets (pad rows carry n_tok=0 -> trash block)
            j2 = len(sfx)
            jpad2 = pow2_bucket(j2)
            t_max = max(len(s_) for _, _, s_, _, _ in sfx)
            tpad = pow2_bucket(t_max)
            stoks = np.zeros((jpad2, tpad), np.int32)
            spos = np.zeros((jpad2,), np.int32)
            sn = np.zeros((jpad2,), np.int32)
            stabs = np.zeros((jpad2, kv.max_blk), np.int32)
            for i, (slot, _, s_, pos0, _) in enumerate(sfx):
                stoks[i, :len(s_)] = s_
                spos[i] = pos0
                sn[i] = len(s_)
                stabs[i] = kv.tables[slot]
            chunk = engine.chunk
            sfx_steps = -(-tpad // chunk) if chunk else tpad
            if engine.mixed and do_decode:
                # ADR-005 fused step: the suffix chunks ride INSIDE the
                # decode window's scan — one sequential pass covers both
                # tiles, so the join/restore adds max(0, chunks - window)
                # scan steps instead of a whole serial prefill dispatch
                mix_steps = max(engine.window, sfx_steps)
                mixed_batch = (self.backend.mixed_fn(
                    kv.bs, chunk, mix_steps, self.donate_kv),
                    jnp.asarray(stoks), jnp.asarray(spos), jnp.asarray(sn),
                    jnp.asarray(stabs))
                sfx_steps = 0
            elif chunk:
                sfx_batch = (self.backend.prefill_window_fn(
                    kv.bs, tpad, self.donate_kv, chunk=chunk),
                    jnp.asarray(stoks), jnp.asarray(spos), jnp.asarray(sn),
                    jnp.asarray(stabs))
            else:
                sfx_batch = (self.backend.prefill_window_fn(
                    kv.bs, tpad, self.donate_kv),
                    jnp.asarray(stoks), jnp.asarray(spos), jnp.asarray(sn),
                    jnp.asarray(stabs))
            nbytes += int(stoks.nbytes)

        if spec and do_decode:
            return functools.partial(
                self._submit_spec_round,
                engine, counts, rows, join_batch, cow_batch, mig_batches,
                sfx_batch, sfx_steps, tables, pos, nbytes)

        def step_fn(params, pool, tok, pos, steps_left, tables):
            for mfn, spool, sids, dids, sslots, dslots in mig_batches:
                pool = mfn(pool, spool, sids, dids, sslots, dslots)
            firsts = None
            if join_batch is not None:
                toks, blks, slots = join_batch
                firsts, pool = prefill_into(params, toks, pool, blks, slots)
            if cow_batch is not None:
                copy_into, src, dst = cow_batch
                pool = copy_into(pool, src, dst)
            firsts_sfx = None
            nxt = None
            if mixed_batch is not None:
                mw, stoks, spos, sn, stabs = mixed_batch
                nxt, firsts_sfx, pool = mw(params, pool, tok, pos,
                                           steps_left, tables,
                                           stoks, spos, sn, stabs)
            else:
                if sfx_batch is not None:
                    pw, stoks, spos, sn, stabs = sfx_batch
                    firsts_sfx, pool = pw(params, pool, stoks, spos, sn,
                                          stabs)
                if do_decode:
                    nxt, pool = decode_window(params, pool, tok, pos,
                                              steps_left, tables)
            return firsts, firsts_sfx, nxt, pool

        # sequential scan steps this dispatch executes — what a step-aware
        # executor bills (benchmarks/serving_load.py's mixed sweep): the
        # batched join prefill and the CoW copy are one parallel pass each;
        # the suffix scan and decode window are sequential scans, fused
        # into max(..) steps by the mixed path instead of added serially
        step_fn.seq_steps = (
            int(join_batch is not None) + int(cow_batch is not None)
            + len(mig_batches)
            + (mix_steps if mixed_batch is not None
               else sfx_steps + (engine.window if do_decode else 0)))
        # prompt tokens the batched co-located prefill folded this step —
        # lets a step-aware executor bill the full prefill compute (the
        # disagg sweep's fairness hinge: chunked partner prefills bill
        # per chunk, so the one-shot batched path must not ride free)
        step_fn.prefill_tokens = (int(join_batch[0].shape[1])
                                  if join_batch is not None else 0)
        # the dispatch span's stats: live decode rows of the slots, and the
        # KV blocks their contexts span at the first decode step (what the
        # decode attention kernel stages, against slots x max_blk reserved)
        step_fn.trace_meta = {"step": engine.step_id, "rows": int(rows.size),
                              "steps": step_fn.seq_steps,
                              "slots": kv.max_slots}
        if do_decode:
            ctx = np.minimum(kv.pos[rows], self.backend.capacity - 1) + 1
            step_fn.trace_meta["kv_blocks"] = int((-(-ctx // kv.bs)).sum())
        delay = (self.autoscaler.clone_ready_delay(engine.clone,
                                                   self.clock.now())
                 + self._net_s(nbytes) + xfer_s)

        def submit():
            task = self.dispatcher.submit(
                engine.clone, step_fn,
                (self.backend.params, kv.pool, tok, pos, steps_left, tables),
                executor=self.executor, extra_delay=delay,
                label="step" if do_decode else "prefill")
            self._charge(engine.clone, task.venue_seconds)
            engine.main_inflight = True
            return task

        return submit

    # ----------------------------------------------------- disagg prefill
    def _submit_disagg_prefill(self, engine: _SlotEngine):
        """Dispatch every pending disagg admission as ONE chunked paged
        prefill on the engine's partner clone (ADR-009).  The partner
        writes into its own scratch pool; the handoff back to the decode
        pool rides the engine's next step as a ``"disagg"``-kind
        migration (billed on ``disagg_link``, optionally int8-compressed
        in flight).  Returns the dispatched task or None."""
        if not (engine.disagg_on and engine.prefill_clone is not None
                and not engine.disagg_inflight and engine.disagg_joins):
            return None
        ppool = engine.prefill_pool
        rows, engine.disagg_joins = engine.disagg_joins, []
        sub = []
        for slot, req, eff, new_ids in rows:
            # bare-length alloc: the scratch pool never indexes prompts,
            # so it yields exactly the decode side's block count
            pslot, p_ids, _, _ = ppool.alloc_slot(len(eff))
            sub.append((slot, req, eff, new_ids, pslot,
                        [int(b) for b in p_ids]))
        engine.submitted_disagg = sub
        j = len(sub)
        jpad = pow2_bucket(j)
        tpad = pow2_bucket(max(len(e) for _, _, e, _, _, _ in sub))
        ptoks = np.zeros((jpad, tpad), np.int32)
        ppos = np.zeros((jpad,), np.int32)
        pn = np.zeros((jpad,), np.int32)
        ptabs = np.zeros((jpad, ppool.max_blk), np.int32)
        for k, (_s, _r, eff, _n, pslot, _p) in enumerate(sub):
            ptoks[k, :len(eff)] = eff
            pn[k] = len(eff)
            ptabs[k] = ppool.tables[pslot]
        chunk = engine.chunk
        if chunk:
            pw = self.backend.prefill_window_fn(ppool.bs, tpad, False,
                                                chunk=chunk)
        else:
            pw = self.backend.prefill_window_fn(ppool.bs, tpad, False)

        def disagg_fn(params, pool, toks, pos0, n_tok, tabs):
            return pw(params, pool, toks, pos0, n_tok, tabs)

        disagg_fn.seq_steps = -(-tpad // chunk) if chunk else tpad
        disagg_fn.prefill_tokens = 0     # chunk-billed via seq_steps
        toks_d = jnp.asarray(ptoks)
        delay = (self.autoscaler.clone_ready_delay(engine.prefill_clone,
                                                   self.clock.now())
                 + self._net_s(int(toks_d.nbytes)))
        task = self.dispatcher.submit(
            engine.prefill_clone, disagg_fn,
            (self.backend.params, ppool.pool, toks_d, jnp.asarray(ppos),
             jnp.asarray(pn), jnp.asarray(ptabs)),
            executor=self.executor, extra_delay=delay,
            label="disagg_prefill")
        self._charge(engine.prefill_clone, task.venue_seconds)
        engine.disagg_inflight = True
        return task

    def _disagg_prefill_done(self, engine: _SlotEngine, task) -> None:
        """Fold a completed partner prefill: stamp TTFT now (the first
        token exists the moment the partner finishes — streaming it back
        costs token bytes, not the KV handoff), then queue each row's
        block copy into the engine's next step as a disagg migration."""
        firsts, ppool_dev = task.value
        if engine.prefill_pool is not None:
            engine.prefill_pool.pool = ppool_dev
        engine.disagg_inflight = False
        sub, engine.submitted_disagg = engine.submitted_disagg, []
        now = self.clock.now()
        firsts = np.asarray(firsts)
        for (slot, req, eff, new_ids, pslot, p_ids), ft in zip(sub,
                                                               firsts):
            req.first_token_t = now
            req.token_ts = [now]
            engine.migrations.append(
                (slot, req, [int(ft)], now, ppool_dev, p_ids, new_ids,
                 pslot, len(eff), "disagg"))

    def _pump(self, engine: _SlotEngine, inflight: Dict) -> None:
        """Submit whatever the engine can run *now*: its own next step
        (unless one is already in flight) and, independently, a partner
        prefill for pending disagg admissions.  The two overlap — the
        decode clone keeps stepping while the partner prefills, which is
        the entire point of the disaggregation (ADR-009)."""
        if engine.step_work() and not engine.main_inflight:
            task = self._submit_engine_step(engine)
            engine.main_inflight = True
            inflight[task] = engine
            self._maybe_hedge(task, engine, inflight)
        pt = self._submit_disagg_prefill(engine)
        if pt is not None:
            inflight[pt] = engine

    # ------------------------------------------------------- speculative
    def _slot_history(self, engine: _SlotEngine, slot: int) -> List[int]:
        """Tokens resident at positions ``0 .. kv.pos[slot] - 1`` of a
        slot — the committed context the draft model's catch-up replays
        (the *current* token at ``kv.pos`` is the decode input, read from
        ``tok_host``, never from here)."""
        s = engine.slots[slot]
        base = np.zeros((self.prompt_pad,), np.int32)
        pr = s.req.prompt
        base[:min(len(pr), self.prompt_pad)] = pr[:self.prompt_pad]
        seq = base.tolist() + list(s.out)
        return seq[:int(engine.kv.pos[slot])]

    def _submit_spec_round(self, engine: _SlotEngine, counts, rows,
                           join_batch, cow_batch, mig_batches, sfx_batch,
                           sfx_steps, tables, pos, nbytes):
        """Dispatch one speculative round: stash the verify closure
        (which carries the round's join/CoW/migration/suffix folds), then
        fire the *draft* dispatch on the cheap-tier partner clone
        (ADR-008).  The verify is submitted when the draft completes —
        or immediately with zero drafts when every row's window clamped
        to k = 0 (capacity edge / one-token budgets), where the verify
        degenerates to a plain decode step."""
        kv = engine.kv
        act = kv.active.astype(bool)
        k_arr = np.maximum(counts - 1, 0).astype(np.int32)
        n_live = jnp.asarray(counts)
        tok_snap = engine.tok_host.copy()
        prefill_into = engine.prefill_into
        v_fn = self.backend.spec_verify_fn(kv.bs)
        params, pool0 = self.backend.params, kv.pool

        def verify_builder(drafts_np):
            x = np.concatenate([tok_snap[:, None],
                                drafts_np.astype(np.int32)], axis=1)

            def step_fn(params, pool, toks, pos, n_live, tables):
                for mfn, spool, sids, dids, sslots, dslots in mig_batches:
                    pool = mfn(pool, spool, sids, dids, sslots, dslots)
                firsts = None
                if join_batch is not None:
                    jtoks, blks, slots = join_batch
                    firsts, pool = prefill_into(params, jtoks, pool, blks,
                                                slots)
                if cow_batch is not None:
                    copy_into, src, dst = cow_batch
                    pool = copy_into(pool, src, dst)
                firsts_sfx = None
                if sfx_batch is not None:
                    pw, stoks, spos, sn, stabs = sfx_batch
                    firsts_sfx, pool = pw(params, pool, stoks, spos, sn,
                                          stabs)
                greedy, pool = v_fn(params, pool, toks, pos, n_live, tables)
                return firsts, firsts_sfx, greedy, pool

            # the chunked verify scores every window position in ONE
            # sequential pass — that is the dispatches-per-token win
            step_fn.seq_steps = (int(join_batch is not None)
                                 + int(cow_batch is not None)
                                 + len(mig_batches) + sfx_steps + 1)
            args = (params, pool0, jnp.asarray(x), pos, n_live, tables)
            return step_fn, args, nbytes + int(x.nbytes)

        engine._verify_builder = verify_builder
        engine._spec_round = k_arr
        if int(k_arr.sum()) == 0:
            return self._submit_spec_verify(
                engine, np.zeros((kv.max_slots, engine.spec_k), np.int32),
                np.zeros((kv.max_slots,), np.int32))
        # --- draft dispatch: catch-up (committed tokens the draft pool
        # has not ingested) + k greedy proposal steps, one jitted call ---
        n_c = np.where(act, kv.pos - engine.dpos, 0).astype(np.int32)
        tcpad = pow2_bucket(max(int(n_c.max()), 1))
        ctoks = np.zeros((kv.max_slots, tcpad), np.int32)
        for slot in rows:
            if n_c[slot] > 0:
                hist = self._slot_history(engine, slot)
                ctoks[slot, :n_c[slot]] = hist[int(engine.dpos[slot]):]
        d_fn = self.backend.spec_draft_fn(kv.bs, tcpad, engine.spec_k)

        def draft_step(dparams, dpool, ctoks, cpos0, n_c, tok, pos,
                       k_live, tables):
            return d_fn(dparams, dpool, ctoks, cpos0, n_c, tok, pos,
                        k_live, tables)

        draft_step.seq_steps = tcpad + engine.spec_k
        draft_step.step_scale = self.draft_cost
        dargs = (self.backend.draft_params, engine.draft_pool,
                 jnp.asarray(ctoks),
                 jnp.asarray(np.where(act, engine.dpos, 0)),
                 jnp.asarray(n_c), jnp.asarray(tok_snap[:, None]),
                 pos, jnp.asarray(k_arr), tables)
        delay = (self.autoscaler.clone_ready_delay(engine.draft_clone,
                                                   self.clock.now())
                 + self._net_s(int(ctoks.nbytes)))
        task = self.dispatcher.submit(
            engine.draft_clone, draft_step, dargs, executor=self.executor,
            extra_delay=delay, label="draft")
        self._charge(engine.draft_clone, task.venue_seconds)
        return task

    def _spec_draft_done(self, engine: _SlotEngine, task):
        """Fold a completed draft dispatch: take the draft pool update
        and the proposals (bench harnesses corrupt them here,
        deterministically), advance the draft cursors past what the
        draft ingested+proposed, and chain the verify dispatch."""
        drafts, dpool = task.value
        engine.draft_pool = dpool
        drafts = np.asarray(drafts, np.int32)
        k_arr = engine._spec_round
        if self.spec_corruption > 0:
            vocab = getattr(getattr(self.backend, "cfg", None),
                            "vocab_size", None)
            flips = self._spec_rng.random(drafts.shape) \
                < self.spec_corruption
            bumped = drafts + 1 if vocab is None else (drafts + 1) % vocab
            drafts = np.where(flips, bumped, drafts).astype(np.int32)
        rows = engine.decode_rows
        if rows is not None:
            engine.dpos[rows] = engine.kv.pos[rows] + k_arr[rows]
        return self._submit_spec_verify(engine, drafts, k_arr)

    def _submit_spec_verify(self, engine: _SlotEngine, drafts: np.ndarray,
                            n_spec: np.ndarray):
        """Dispatch the stashed verify closure with the round's draft
        proposals — or all-zero drafts with ``n_spec = 0`` when the
        draft clone died mid-round: the verify then accepts nothing and
        emits exactly one plain greedy token per row, preserving the
        round's join/CoW/migration folds (the cohort never stalls)."""
        builder, engine._verify_builder = engine._verify_builder, None
        step_fn, args, nbytes = builder(drafts)
        engine.spec_pending = (drafts, np.asarray(n_spec, np.int32))
        delay = (self.autoscaler.clone_ready_delay(engine.clone,
                                                   self.clock.now())
                 + self._net_s(nbytes))
        task = self.dispatcher.submit(
            engine.clone, step_fn, args, executor=self.executor,
            extra_delay=delay, label="step")
        self._charge(engine.clone, task.venue_seconds)
        return task

    def _engine_step_done(self, engine: _SlotEngine, task,
                          completions: List[ServeCompletion]) -> bool:
        """Fold a completed step back into host state (the
        ``serve.step.fold`` span).  True while alive."""
        n = len(completions)
        with span("serve.step.fold", step=engine.step_id) as sp:
            alive = self._fold_engine_step(engine, task, completions)
            sp.set_metadata(finished=len(completions) - n)
        return alive

    def _fold_engine_step(self, engine: _SlotEngine, task,
                          completions: List[ServeCompletion]) -> bool:
        now = self.clock.now()
        firsts, firsts_sfx, nxt, pool = task.value
        kv = engine.kv
        kv.pool = pool
        firsts = [] if firsts is None else np.asarray(firsts)
        for (slot, req, _, _), ft in zip(engine.submitted_joins, firsts):
            t0 = int(ft)
            engine.slots[slot] = _Slot(req, [t0], now, token_ts=[now])
            engine.tok_host[slot] = t0
            engine.dpos[slot] = 0       # draft replays full history
            kv.active[slot] = True
        engine.submitted_joins = []
        firsts_sfx = [] if firsts_sfx is None else np.asarray(firsts_sfx)
        for (slot, req, _, _, restore), ft in zip(engine.submitted_sfx,
                                                  firsts_sfx):
            if restore:
                # resume where preemption stopped: generated tokens were
                # already emitted (TTFT stamp preserved), the last one is
                # the next decode input — the scan's final logits only
                # re-derive it, so the stored token is authoritative
                t0 = int(req.generated[-1])
                engine.slots[slot] = _Slot(
                    req, list(req.generated), req.first_token_t,
                    token_ts=_carried_ts(req, len(req.generated)))
            else:
                t0 = int(ft)
                engine.slots[slot] = _Slot(req, [t0], now, token_ts=[now])
            engine.tok_host[slot] = t0
            engine.dpos[slot] = 0       # draft replays full history
            kv.active[slot] = True
        engine.submitted_sfx = []
        for m in engine.submitted_migrations:
            slot, req, out, ft = m[0], m[1], m[2], m[3]
            kind = m[9] if len(m) > 9 else "recover"
            # the migrated slot resumes exactly where the source clone
            # stopped: tokens already emitted, the last one is the next
            # decode input (same contract as the restore fold above)
            engine.slots[slot] = _Slot(req, list(out), ft,
                                       token_ts=_carried_ts(req, len(out)))
            engine.tok_host[slot] = int(out[-1])
            engine.dpos[slot] = 0       # draft replays full history
            kv.active[slot] = True
            if kind == "disagg":
                # handoff landed: the partner's scratch slot retires and
                # the slot's prompt blocks leave the pending set below —
                # they are real and shareable from this fold on
                self.disagg_handoffs += 1
                engine.disagg_blocks.pop(slot, None)
                if engine.prefill_pool is not None:
                    engine.prefill_pool.free_slot(m[7])
            else:
                self.recoveries_migrated += 1
        engine.submitted_migrations = []
        kv.clear_pending()
        # disagg slots still awaiting their handoff copy keep their
        # prompt blocks un-shareable: clear_pending() is index-global, so
        # re-pin them until the fold above retires each slot (ADR-009)
        for ids in engine.disagg_blocks.values():
            kv._pending.update(int(b) for b in ids)
        if engine.decode_rows is not None and nxt is not None:
            nxt = np.asarray(nxt)                       # (S, window)
            rows = engine.decode_rows
            spec_pend, engine.spec_pending = engine.spec_pending, None
            if spec_pend is not None:
                # speculative fold (ADR-008): the verify grid scored the
                # current token plus every draft; accept the longest
                # agreeing prefix and emit one extra target token — the
                # emitted stream is bitwise the stepwise greedy stream
                drafts, n_spec = spec_pend
                acc = model.spec_accept(nxt, drafts, n_spec)[rows]
                n = (acc + 1).astype(np.int32)
                self.spec_rounds += 1
                self.spec_proposed += int(n_spec[rows].sum())
                self.spec_accepted += int(acc.sum())
                self.spec_tokens += int(n.sum())
                for slot, a in zip(rows, acc.tolist()):
                    k_i = int(n_spec[slot])
                    if k_i > 0:     # EMA drives next round's window K
                        req = engine.slots[slot].req
                        req.spec_ema = (0.5 * req.spec_ema
                                        + 0.5 * (a / k_i))
            else:
                n = engine.decode_counts[rows]          # >= 1 per active row
            # vectorized fold: last live token and the capacity clamp via
            # fancy indexing (the clamp: past capacity the write position
            # pins to the last slot, like the contiguous path, so the
            # written-token count must not keep growing either)
            engine.tok_host[rows] = nxt[rows, n - 1]
            kv.pos[rows] = np.minimum(kv.pos[rows] + n, kv.capacity)
            # draft cursor never runs ahead of the committed context:
            # rejected proposals' KV is garbage on both pools, the next
            # catch-up overwrites it
            engine.dpos[rows] = np.minimum(engine.dpos[rows], kv.pos[rows])
            if spec_pend is not None:
                engine.spec_rounds_done += 1
                self._maybe_drop_speculation(engine)
            # streamed delivery stamps: tokens leave the clone spread
            # across the dispatch interval, so interpolate within
            # [submitted_at, done_at] per row (ADR-007 TTFT/TPOT)
            t0 = getattr(task, "submitted_at", now)
            span = max(now - t0, 0.0)
            for slot, row, k in zip(rows, nxt[rows].tolist(), n.tolist()):
                engine.slots[slot].out.extend(row[:k])
                engine.slots[slot].token_ts.extend(
                    t0 + span * (j + 1) / k for j in range(k))
            engine.decode_rows = None
        for slot, s in enumerate(engine.slots):   # evict at step granularity
            if s is not None and len(s.out) >= s.req.max_new_tokens:
                self.tokens_emitted += len(s.out)
                completions.append(ServeCompletion(
                    s.req.rid, s.out, s.req.arrival_t, s.first_token_t,
                    now, engine.clone.spec.name,
                    tenant=s.req.tenant, slo=s.req.slo,
                    deadline_s=s.req.deadline_s,
                    token_ts=(s.token_ts if len(s.token_ts) == len(s.out)
                              else [])))
                t = engine.clone.ctype.name
                self.fleet_mix[t] = self.fleet_mix.get(t, 0) + 1
                engine.slots[slot] = None
                kv.free_slot(slot)
        return engine.alive()

    def _maybe_drop_speculation(self, engine: _SlotEngine) -> None:
        """Adaptive bail-out (ADR-008): when the cohort's mean
        acceptance EMA collapses, speculation costs more dispatches than
        it saves — release the draft clone and fall back to the plain
        decode window for this engine (sticky; counted as a fallback)."""
        if not engine.spec_on or engine.spec_rounds_done < 3:
            return
        emas = [s.req.spec_ema for s in engine.slots if s is not None]
        if emas and float(np.mean(emas)) < 0.25:
            engine.spec_on = False
            self.spec_fallbacks += 1
            if engine.draft_clone is not None:
                self.pool.release([engine.draft_clone])
                engine.draft_clone = None

    # ------------------------------------------------------- fault recovery
    def _requeue_lost(self, req: ServeRequest) -> None:
        """Send a dead engine's request back through admission on the
        prefix-accelerated restore path (its ``generated`` tokens, if
        any, were already carried onto the request)."""
        req.preemptions += 1
        self.queue.requeue(req)
        self.recoveries_restored += 1

    def _try_migrate(self, src_engine: _SlotEngine, slot: int, s: _Slot,
                     engines: Dict[int, "_SlotEngine"]) -> bool:
        """Queue one active slot of a draining engine for KV migration
        into a survivor with room: claim a destination slot + blocks now
        (so later candidates in the same recovery pass see the
        commitment), defer the device copy into the destination's next
        step closure.  False when no survivor can admit the context."""
        if getattr(self.backend, "migrate_fn", None) is None:
            return False
        kv = src_engine.kv
        pos = int(kv.pos[slot])
        nb = (pos - 1) // kv.bs + 1
        src_ids = [int(b) for b in kv.tables[slot, :nb]]
        cands = sorted(
            (e for e in engines.values()
             if e is not src_engine and e.clone.serveable),
            key=lambda e: e.clone.cid)
        for dst in cands:
            if not dst.kv.can_admit(pos, s.req.max_new_tokens):
                continue
            dslot, new_ids, _, _ = dst.kv.alloc_slot(pos)
            s.req.token_ts = list(s.token_ts)   # stamps survive the move
            dst.migrations.append(
                (dslot, s.req, list(s.out), s.first_token_t,
                 kv.pool, src_ids, [int(b) for b in new_ids], slot, pos,
                 "recover"))
            return True
        return False

    def _recover_engine(self, engine: _SlotEngine, fault: CloneFault,
                        engines: Dict[int, "_SlotEngine"]) -> None:
        """Recover every request a dead engine held (ADR-006).

        Pending/submitted joins and inbound migrations never folded a
        token on this engine, so they simply requeue (suffix/migration
        rows carry their generated tokens).  *Active* slots hold real
        decode progress: a ``drain`` leaves the KV salvageable — migrate
        to a survivor when one can admit the context — while a ``kill``
        lost the device memory, so the request requeues on the restore
        path and re-prefills (prefix-accelerated on a surviving pool).
        """
        for (_, req, _t, _b) in engine.joins + engine.submitted_joins:
            self._requeue_lost(req)
        for (_, req, _s, _p, _r) in engine.sfx_joins + engine.submitted_sfx:
            self._requeue_lost(req)
        for (_, req, out, ft, *_rest) in (engine.migrations
                                          + engine.submitted_migrations):
            req.generated = list(out)
            req.first_token_t = ft
            self._requeue_lost(req)
        # disagg rows parked on the partner never folded a token on THIS
        # engine either: requeue them cold (the partner's scratch pool
        # is transient — nothing to salvage from the decode side)
        for (_, req, _e, _i) in engine.disagg_joins:
            self._requeue_lost(req)
        for (_, req, _e, _i, _ps, _pi) in engine.submitted_disagg:
            self._requeue_lost(req)
        engine.joins, engine.sfx_joins, engine.cow_pairs = [], [], []
        engine.submitted_joins, engine.submitted_sfx = [], []
        engine.migrations, engine.submitted_migrations = [], []
        engine.disagg_joins, engine.submitted_disagg = [], []
        engine.disagg_blocks = {}
        for slot, s in enumerate(engine.slots):
            if s is None:
                continue
            if not (fault.kind == "drain"
                    and self._try_migrate(engine, slot, s, engines)):
                s.req.generated = list(s.out)
                s.req.first_token_t = s.first_token_t
                s.req.token_ts = list(s.token_ts)
                self._requeue_lost(s.req)
            engine.slots[slot] = None
        # the pool object dies with the clone — a revived clone starts
        # from a fresh pool (its prefix index died with the memory); the
        # device arrays stay referenced by any pending migration tuples
        self._kv_pools.pop(engine.clone.cid, None)
        self._prefill_pools.pop(engine.clone.cid, None)

    def _recover_failed(self, inflight: Dict,
                        engines: Dict[int, "_SlotEngine"]) -> None:
        """Handle every clone the injector killed/drained since the last
        round: cancel its in-flight dispatches (their values will never
        arrive), resolve hedge races, and recover its engine's requests."""
        for clone, fault in self.injector.drain_failed():
            draft_orphans = []        # engines whose draft died mid-round
            disagg_orphans = []       # engines whose partner died mid-prefill
            for task in [t for t in inflight if t.clone is clone]:
                unit = inflight.pop(task)
                self.dispatcher.cancel(task)
                if task.label == "draft":
                    # the VERIFY closure (and this round's join/CoW/
                    # migration folds) is stashed on the engine — it can
                    # still run, with zero drafts, on the healthy clone
                    draft_orphans.append(unit)
                    continue
                if task.label == "disagg_prefill":
                    # the decode engine is healthy — its parked rows
                    # requeue / degrade to co-located prefill below
                    disagg_orphans.append(unit)
                    continue
                partner = self._hedges.pop(task, None)
                if partner is not None:
                    self._hedges.pop(partner, None)
                    if task.label == "hedge":
                        continue      # the original keeps racing
                    # the engine's own step died with the clone — its
                    # hedge can't rescue an engine being recovered
                    inflight.pop(partner, None)
                    self.dispatcher.cancel(partner)
                    self.pool.release([partner.clone])
            engine = None
            for key, eng in list(engines.items()):
                if eng.clone is clone:
                    engine = engines.pop(key)
                    self.ledger.drop(key)
                    break
            if engine is not None:
                # a dead verify clone orphans its in-flight draft
                # dispatch (the verify target is gone) and frees the
                # draft partner back to the pool
                for t in [t for t, u in inflight.items() if u is engine]:
                    inflight.pop(t)
                    self.dispatcher.cancel(t)
                if engine.draft_clone is not None:
                    self.pool.release([engine.draft_clone])
                    engine.draft_clone = None
                if engine.prefill_clone is not None:
                    engine.prefill_clone = None
                    engine.disagg_on = False
                    self._release_partner()
                self._recover_engine(engine, fault, engines)
            self.pool.release([clone])
            # draft-clone death degrades its engines to plain decode —
            # they never stall (ADR-008): an interrupted round completes
            # as a zero-draft verify (accepts nothing, emits one plain
            # greedy token per row, folds the round's joins/migrations)
            for eng in engines.values():
                if eng.draft_clone is clone:
                    eng.draft_clone = None
                    eng.spec_on = False
                    self.spec_fallbacks += 1
            for eng in draft_orphans:
                if id(eng) in engines and eng._verify_builder is not None:
                    vt = self._submit_spec_verify(
                        eng,
                        np.zeros((eng.kv.max_slots, max(eng.spec_k, 1)),
                                 np.int32),
                        np.zeros((eng.kv.max_slots,), np.int32))
                    inflight[vt] = eng
            # partner-clone death degrades its engines to co-located
            # prefill (ADR-009): rows mid-flight on the dead partner
            # requeue (no token was ever emitted for them); rows still
            # pending convert to plain joins on the decode clone — the
            # engine never stalls
            for eng in disagg_orphans:
                if id(eng) not in engines:
                    continue            # decode engine died too: handled
                eng.disagg_inflight = False
                sub, eng.submitted_disagg = eng.submitted_disagg, []
                for (slot, req, _e, _i, _ps, _pi) in sub:
                    eng.disagg_blocks.pop(slot, None)
                    eng.kv.cancel_slot(slot)
                    self._requeue_lost(req)
            for eng in engines.values():
                if eng.prefill_clone is not clone:
                    continue
                eng.prefill_clone = None
                eng.prefill_pool = None
                eng.disagg_on = False
                self.disagg_fallbacks += 1
                # the scratch pool's device arrays died with the partner
                self._prefill_pools.pop(eng.clone.cid, None)
                rows, eng.disagg_joins = eng.disagg_joins, []
                for (slot, req, eff, ids) in rows:
                    eng.disagg_blocks.pop(slot, None)
                    eng.joins.append(
                        (slot, req, jnp.asarray(eff[None]),
                         jnp.asarray(np.asarray(ids, np.int32))))
                self._pump(eng, inflight)
            if self._partner_clone is clone:
                # injector-killed partner: every engine's reference died
                # with it (pool.release of the dead clone ran above)
                self._partner_clone = None
                self._partner_refs = 0

    # ---------------------------------------------------------------- hedge
    def _maybe_hedge(self, task, engine: _SlotEngine,
                     inflight: Dict) -> None:
        """Race a straggling decode step on a second clone (ADR-006).

        A step whose timeline duration exceeds the recent-history
        quantile by ``hedge_factor`` gets its (pure) closure re-issued
        on a free serveable clone; the duplicate pays the engine's live
        KV context over the link up front.  Whichever copy completes
        first is folded; the loser's completion event is cancelled."""
        if self.hedge_factor <= 0 or task.label != "step":
            return
        hist = self._step_hist
        fire = (len(hist) >= self.hedge_min_samples
                and task.duration > self.hedge_factor
                * float(np.quantile(hist, self.hedge_quantile)))
        hist.append(task.duration)    # after the decision: never vs itself
        if not fire:
            return
        clone = self._free_clone()
        if clone is None or clone is engine.clone:
            return
        kv = engine.kv
        ctx_tokens = int(kv.pos[kv.active].sum())
        delay = (self.autoscaler.clone_ready_delay(clone, self.clock.now())
                 + self._net_s(int(ctx_tokens * self._kv_token_bytes())))
        clone.busy = True
        dup = self.dispatcher.submit(clone, task.fn, task.fn_args,
                                     executor=self.executor,
                                     extra_delay=delay, label="hedge")
        self._charge(clone, dup.venue_seconds)
        self.hedges_fired += 1
        self._hedges[task] = dup
        self._hedges[dup] = task
        inflight[dup] = engine

    def _resolve_hedge(self, winner, inflight: Dict) -> None:
        """First of a hedge pair completed: cancel the loser, return the
        borrowed clone, score the win if the duplicate got there first."""
        partner = self._hedges.pop(winner, None)
        if partner is None:
            return
        self._hedges.pop(partner, None)
        inflight.pop(partner, None)
        self.dispatcher.cancel(partner)
        hedge = winner if winner.label == "hedge" else partner
        self.pool.release([hedge.clone])
        if winner is hedge:
            self.hedge_wins += 1

    # ------------------------------------------------------------------ run
    def run(self, requests: List[ServeRequest], *,
            drain_idle_s: float = 0.0) -> ServeReport:
        """Serve ``requests`` on the virtual timeline; returns a report.

        The loop (both KV modes): admit due arrivals into the bounded
        queue; in paged mode, *offer queued requests to partially-full
        in-flight engines first* (the :class:`~repro.core.scheduler.
        SlotLedger` admission policy — mid-flight joins); autoscale on the
        residual demand; start new engines/cohorts on free clones; then
        advance time to the next task completion or arrival.
        """
        paged = self.kv_mode == "paged"
        reqs = sorted(requests, key=lambda r: r.arrival_t)
        t_start = self.clock.now()
        i = 0
        inflight: Dict[object, object] = {}        # task -> engine | cohort
        engines: Dict[int, _SlotEngine] = {}       # id -> live engine
        completions: List[ServeCompletion] = []
        notified = 0                    # completions fed back to the gateway
        if self.injector is not None:
            self.injector.arm()             # faults become clock events

        while True:
            now = self.clock.now()
            self._band_cache.clear()        # fresh round, fresh inventory
            if i < len(reqs) and reqs[i].arrival_t <= now + 1e-12:
                with span("serve.arrivals") as sp:
                    i0 = i
                    while i < len(reqs) and reqs[i].arrival_t <= now + 1e-12:
                        # arrivals flow through the gateway when one is
                        # present (ADR-007); it decides cache-hit / reject
                        # / shed / queue and releases into self.queue
                        # under quota + fair share
                        if self.gateway is not None:
                            self.gateway.offer(reqs[i], now)
                        else:
                            self.queue.offer(reqs[i], now)
                        i += 1
                    sp.set_metadata(n=i - i0)
            if self.injector is not None:
                # recover clones that died since the last round BEFORE
                # joins/spawns consult the engine set (ADR-006)
                self._recover_failed(inflight, engines)
            if self.gateway is not None:
                gw = self.gateway
                # fleet census AFTER recovery: serveable = healthy clones
                # with closed breakers — breaker opens and DEAD clones
                # shrink the gateway's admission envelope (ADR-006 signal)
                healthy = sum(1 for c in self.pool.clones if c.serveable)
                gw.observe_fleet(healthy, len(self.pool.clones),
                                 self.max_batch * max(healthy, 1))
                while notified < len(completions):
                    gw.observe_completion(completions[notified])
                    notified += 1
                gw.release(now, self.queue,
                           self.queue.max_depth - self.queue.depth)
                completions.extend(gw.drain_cached())
            self._peak_queue_depth = max(
                self._peak_queue_depth,
                self.queue.depth + (self.gateway.queued
                                    if self.gateway is not None else 0))
            if paged and engines:
                # mid-flight joins: fill open slots of in-flight engines
                # before counting residual demand or spawning new ones
                # (block-commitment checked per request via ``fits``)
                for key, eng in engines.items():
                    self.ledger.update(key, eng.kv.free_slots)
                # admit via on_assign so each fits() check sees the block
                # allocations of earlier assignments in the same round;
                # fits() matches the effective prompt against the prefix
                # index, so a shared-prefix request needs only its
                # private blocks free — and vetoes engines outside the
                # request's placement band (ADR-004)
                # affinity routing scores candidate engines by cached-
                # prefix depth (ADR-009); random is the ablation arm
                prefer = None
                if self.routing == "affinity":
                    prefer = (lambda key, r:
                              float(self._affinity_depth(engines[key].kv,
                                                         r)))
                elif self.routing == "random":
                    prefer = lambda key, r: float(self._route_rng.random())
                self.ledger.assign(
                    self.queue,
                    fits=lambda key, r: self._fits_slot(engines[key], r),
                    on_assign=lambda key, r: self._admit(engines[key], r),
                    prefer=prefer)
                # parked engines (only partner work was in flight) may
                # have gained runnable work from the assignments
                for eng in engines.values():
                    self._pump(eng, inflight)
            # demand bucketed per tenant/priority class and KV tier; the
            # placement engine turns buckets into per-type targets
            self.autoscaler.step(now, self._demand_buckets(),
                                 self._in_flight_by_type(inflight))
            # spawn engines/cohorts while an adequate clone is free
            while self.queue.depth > 0:
                # first queued request some free clone can serve: a head
                # whose tier is still provisioning (a booting ``large``)
                # must not head-of-line-block the bulk behind it
                picked = clone = None
                for r in self.queue.snapshot():
                    lo, hi = self._placement_band(r)
                    pc = (self._best_affinity_cid(r)
                          if self.routing == "affinity" else None)
                    clone = self._free_clone(lo, hi, prefer_cid=pc)
                    if clone is not None:
                        picked = r
                        break
                if clone is None:
                    break
                if paged:
                    engine = self._start_engine(clone)

                    # the picked request bypasses the band re-check: the
                    # clone was chosen *for it*, and starting the engine
                    # marks the clone busy, which already shifts the
                    # inventory-dependent placement band
                    def fill(r, picked=picked, engine=engine):
                        if r is picked:
                            return engine.kv.can_admit(
                                _SlotEngine.effective_prompt(
                                    r, self.prompt_pad,
                                    self.backend.capacity),
                                r.max_new_tokens)
                        return self._fits_slot(engine, r)

                    n = 0
                    while n < self.max_batch and self.queue.depth > 0:
                        req = self.queue.take_where(fill)
                        if req is None:
                            break
                        self._admit(engine, req)
                        n += 1
                    if n == 0:
                        raise RuntimeError(
                            "KV block pool too small to hold one request's "
                            "prompt even when empty — preemption has no "
                            f"victim (num_blocks={engine.kv.num_blocks}, "
                            f"prompt_pad={self.prompt_pad}, "
                            f"block_size={self.block_size})")
                    engines[id(engine)] = engine
                    self.ledger.update(id(engine), engine.kv.free_slots)
                    self._pump(engine, inflight)
                else:
                    # the cohort seeds with the *picked* request (the
                    # clone was banded for it — never the possibly
                    # band-blocked FIFO head) and fills with band-
                    # compatible requests in FIFO order
                    batch = []
                    while len(batch) < self.max_batch:
                        req = self.queue.take_where(
                            lambda r: r is picked or self._in_band(r,
                                                                   clone))
                        if req is None:
                            break
                        batch.append(req)
                    task, cohort = self._start_cohort(batch, clone)
                    inflight[task] = cohort
                # spawning changed the pool inventory (clone now busy):
                # placement bands must be re-derived next evaluation
                self._band_cache.clear()

            if inflight:
                # bound the wait so due arrivals are admitted on time and
                # a mid-window clone death is detected when it fires, not
                # when the doomed dispatch would have completed
                next_arrival = reqs[i].arrival_t if i < len(reqs) else None
                next_fault = (self.injector.next_event_time()
                              if self.injector is not None else None)
                next_gw = (self.gateway.next_event_time()
                           if self.gateway is not None else None)
                bound = min((t for t in (next_arrival, next_fault, next_gw)
                             if t is not None and t > now), default=None)
                first_done = min(t.done_at for t in inflight)
                if bound is not None and bound < first_done:
                    with span("serve.wait"):
                        self.clock.advance_to(bound)
                    continue
                with span("serve.wait"):
                    done = self.dispatcher.wait_any(list(inflight))
                for task in done:
                    unit = inflight.pop(task, None)
                    if unit is None:
                        continue          # hedge loser already resolved
                    self._resolve_hedge(task, inflight)
                    if paged:
                        if task.label == "draft":
                            # half-round: chain the verify on the target
                            vt = self._spec_draft_done(unit, task)
                            inflight[vt] = unit
                            self._maybe_hedge(vt, unit, inflight)
                            continue
                        if task.label == "disagg_prefill":
                            # partner done: the handoff copy rides the
                            # engine's next step (pumped now — the engine
                            # may have been parked waiting on this)
                            self._disagg_prefill_done(unit, task)
                            self._pump(unit, inflight)
                            continue
                        unit.main_inflight = False
                        if self._engine_step_done(unit, task, completions):
                            self._pump(unit, inflight)
                        else:
                            engines.pop(id(unit), None)
                            self.ledger.drop(id(unit))
                            self._release_engine(unit)
                    else:
                        cohort = unit
                        tok, cohort.cache = task.value
                        cohort.tok = tok[:, None]
                        if cohort.phase == "prefill":
                            cohort.phase = "decode"
                        else:
                            cohort.step += 1
                        if self._retire(cohort, completions):
                            inflight[self._submit_decode(cohort)] = cohort
            elif i < len(reqs):
                with span("serve.wait"):
                    self.clock.advance_to(reqs[i].arrival_t)
            elif self.queue.depth > 0:
                # every clone may be dead/tripped with revival + probe
                # events pending — advance to the next clock event and
                # let the breaker half-open probes re-admit capacity
                nxt = (self.clock.next_event_time()
                       if self.injector is not None else None)
                if nxt is not None and nxt > now + 1e-12:
                    self.clock.advance_to(nxt)
                    continue
                raise RuntimeError("requests queued but no clone can run "
                                   "(max_secondaries too small?)")
            elif self.gateway is not None and self.gateway.pending > 0:
                # the gateway still owes work: a quota-blocked head (its
                # bucket's eta) or a scheduled Retry-After replay —
                # advance to the event that unblocks it
                nxt = self.gateway.next_event_time()
                if nxt is None:
                    nxt = self.clock.next_event_time()
                if nxt is not None and nxt > now + 1e-12:
                    self.clock.advance_to(nxt)
                    continue
                raise RuntimeError("gateway holds queued work but no clock "
                                   "event can release it")
            else:
                break

        if drain_idle_s > 0.0:       # let idle TTLs pause the secondaries
            # step the drain in PAUSE_IDLE_TTL chunks so the *second* TTL
            # stage fires too: a clone pauses once idle > PAUSE_IDLE_TTL
            # and powers off only on a later reap with idle > OFF_IDLE_TTL
            # — one big advance would pause but never power off
            end = self.clock.now() + drain_idle_s
            while self.clock.now() < end - 1e-9:
                self.clock.advance(min(PAUSE_IDLE_TTL,
                                       end - self.clock.now()))
                self.pool.reap_idle()
            self.autoscaler.step(self.clock.now(), [], {})

        lat = np.array([c.latency_s for c in completions]) \
            if completions else np.zeros(1)
        ttft = np.array([c.ttft_s for c in completions]) \
            if completions else np.zeros(1)
        makespan = self.clock.now() - t_start - drain_idle_s
        utils = [w / r for w, r in self.kv_samples if r > 0]
        cs_by_type = self.pool.clone_seconds_by_type(self.clock.now())
        # SLO accounting over *offered* requests (ADR-007): work the
        # gateway rejected, shed, or dropped counts as missed
        offered_by_slo: Dict[str, int] = {}
        for r in reqs:
            offered_by_slo[r.slo] = offered_by_slo.get(r.slo, 0) + 1
        met_by_slo: Dict[str, int] = {}
        for c in completions:
            if c.met_deadline:
                met_by_slo[c.slo] = met_by_slo.get(c.slo, 0) + 1
        slo_attainment = {s: met_by_slo.get(s, 0) / n
                          for s, n in offered_by_slo.items() if n}
        good_tokens = sum(len(c.tokens) for c in completions
                          if c.met_deadline)
        by_tenant: Dict[str, List[ServeCompletion]] = {}
        for c in completions:
            by_tenant.setdefault(c.tenant or "", []).append(c)
        per_tenant = {
            t: {"served": float(len(cs)),
                "p50_ttft_s": float(np.percentile(
                    [c.ttft_s for c in cs], 50)),
                "p50_tpot_s": float(np.percentile(
                    [c.tpot_s for c in cs], 50))}
            for t, cs in sorted(by_tenant.items())}
        gw = self.gateway
        per_clone = {
            str(cid): {
                "type": st["type"],
                "prefix_hit_rate": (st["prefix_hit_tokens"]
                                    / max(st["prompt_tokens"], 1)),
                "kv_transfer_bytes": float(st["kv_transfer_bytes"]),
                "kv_transfer_s": float(st["kv_transfer_s"])}
            for cid, st in sorted(self.per_clone_stats.items())}
        return ServeReport(
            completions=completions,
            accepted=self.queue.accepted,
            rejected=self.queue.rejected,
            makespan_s=makespan,
            p50_latency_s=float(np.percentile(lat, 50)),
            p99_latency_s=float(np.percentile(lat, 99)),
            p50_ttft_s=float(np.percentile(ttft, 50)),
            tokens_per_s=self.tokens_emitted / max(makespan, 1e-9),
            peak_secondaries=self.autoscaler.peak_secondaries,
            scale_ups=self.autoscaler.scale_ups,
            busy_energy_j=self.busy_energy_j,
            pool_stats=dict(self.pool.stats),
            clone_samples=list(self.autoscaler.samples),
            kv_mode=self.kv_mode,
            kv_util=float(np.mean(utils)) if utils else 0.0,
            kv_reserved_peak=max((r for _, r in self.kv_samples),
                                 default=0),
            prefix_hit_rate=(self.prefix_hit_tokens
                             / max(self.prompt_tokens, 1)),
            preemptions=self.preemptions,
            restored_tokens=self.restored_tokens,
            fleet_mix=dict(self.fleet_mix),
            escalations=len(self._escalated),
            clone_seconds_by_type=cs_by_type,
            cost_usd=self.pool.cost_usd(self.clock.now()),
            energy_j_by_type=dict(self.energy_j_by_type),
            power_offs=self.pool.stats["offs"],
            faults_injected=(self.injector.stats["injected"]
                             if self.injector is not None else 0),
            recoveries_migrated=self.recoveries_migrated,
            recoveries_restored=self.recoveries_restored,
            hedges_fired=self.hedges_fired,
            hedge_wins=self.hedge_wins,
            breaker_opens=sum(c.breaker.opens for c in self.pool.clones),
            slo_attainment=slo_attainment,
            goodput_tps=good_tokens / max(makespan, 1e-9),
            gateway_shed=gw.shed if gw is not None else 0,
            gateway_rejected=gw.rejected if gw is not None else 0,
            gateway_retries=gw.retries if gw is not None else 0,
            cache_hits=gw.cache_hits if gw is not None else 0,
            shed_by_slo=dict(gw.shed_by_slo) if gw is not None else {},
            per_tenant=per_tenant,
            peak_queue_depth=self._peak_queue_depth,
            spec_rounds=self.spec_rounds,
            spec_tokens=self.spec_tokens,
            acceptance_rate=(self.spec_accepted
                             / max(self.spec_proposed, 1)),
            spec_fallbacks=self.spec_fallbacks,
            disagg_handoffs=self.disagg_handoffs,
            disagg_colocated=self.disagg_colocated,
            disagg_fallbacks=self.disagg_fallbacks,
            kv_transfer_bytes=self.kv_transfer_bytes,
            kv_transfer_s=self.kv_transfer_s,
            per_clone=per_clone)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--policy", default="exec_time")
    ap.add_argument("--handler", action="store_true",
                    help="serve through the event-driven ClientHandler")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson offered load (req/s) for --handler")
    ap.add_argument("--kv", choices=["paged", "contiguous"], default="paged",
                    help="KV cache mode for --handler")
    ap.add_argument("--window", type=int, default=1,
                    help="decode window: tokens fused per device dispatch")
    ap.add_argument("--full", action="store_true",
                    help="serve the config at its published widths (random "
                         "weights from --seed) on a wall clock, instead of "
                         "the reduced smoke config")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg)
    if args.handler:
        backend = LMBackend(cfg, capacity=64, seed=args.seed)
        handler = ClientHandler(backend, max_batch=args.batch, kv=args.kv,
                                decode_window=args.window,
                                executor=(measured_executor if args.full
                                          else None))
        reqs = poisson_arrivals(args.rate, args.requests,
                                prompt_len=8, vocab=cfg.vocab_size,
                                max_new_tokens=args.new_tokens)
        report = handler.run(reqs, drain_idle_s=60.0)
        print(report.summary())
        print("pool:", report.pool_stats)
        return

    eng = ServingEngine(cfg, policy=Policy(args.policy),
                        backend=LMBackend(cfg, seed=args.seed))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=12,
                                    dtype=np.int32), args.new_tokens)
            for i in range(args.requests)]
    done = []
    for i in range(0, len(reqs), args.batch):
        comps = eng.serve_batch(reqs[i:i + args.batch])
        done.extend(comps)
        c = comps[0]
        print(f"batch {i // args.batch}: venue={c.prefill_venue} "
              f"latency={c.latency_s:.3f}s tokens={c.tokens[:6]}...")
    print("stats:", eng.stats)


if __name__ == "__main__":
    main()
