"""Serving under offered load: the Client Handler's elasticity, measured.

Sweeps Poisson arrival rates against the event-driven continuous-batching
``ClientHandler`` (paper §5.2-§5.3) on the virtual timeline, in both KV
cache modes, and reports per (rate, mode): p50/p99 request latency, p50
time-to-first-token, throughput (tokens/s), client-side shed rate,
clone-pool activity (resumes/boots/pauses), busy energy, the autoscaler's
peak secondary count, KV memory utilization (written / reserved tokens),
and the prefix-cache economics (hit rate, preemptions, restored tokens).
``paged`` admits late arrivals into free slots of in-flight engines
(per-slot decode cursors over a block pool); ``contiguous`` is the
step-boundary-fusion baseline.  Every level ends with an idle drain past
the pause TTL so the elastic shrink is visible too.

Two dedicated sweeps measure the ADR-003 refactor directly:

- **shared-prefix sweep** (``--prefix-len``/``--prefix-share``): a common
  system prompt across requests, served with the prefix cache on vs off
  (the measurable un-shared baseline) on one trace — hit rate, TTFT, and
  physical KV reservation are the headline columns.
- **tight-pool sweep** (``--tight-blocks``): a deliberately
  under-provisioned ``KVBlockPool``; the run must complete every request
  via preemption + prefix-accelerated restore (zero RuntimeError), where
  worst-case-reservation admission would refuse or serialize.

A fourth sweep measures the ADR-005 unified mixed prefill/decode dispatch:

- **mixed-dispatch sweep** (``--mixed-joins``): a decode cohort joined
  mid-stream by shared-prefix arrivals, served three ways on one trace —
  no joins at all (baseline), serial stepwise prefill-then-decode, and
  chunked prefill fused into the decode window.  The executor charges
  venue time per *sequential scan step* (``seq_steps``), so the serial
  path's prefill stall is visible in the decode cohort's p99 TPOT while
  the fused path must hold TPOT at the no-join baseline,
  token-identically.

A sixth sweep measures the ADR-007 SLO-aware gateway:

- **overload sweep** (``--overload-requests``, ``--link``): one
  multi-tenant trace per offered-load multiple of the fleet's capacity
  ceiling, served ungated (unbounded queue) vs through the
  ``StreamingGateway``; past ~1.5x capacity the ungated p99 TTFT and
  queue depth diverge while the gateway holds interactive SLO
  attainment >= 95% by shedding only batch work, token-identically for
  everything admitted; a final pair adds a mid-run clone kill (ADR-006
  injector) under overload.

A third dedicated sweep measures the ADR-004 heterogeneous fleet:

- **fleet sweep** (``--fleet``, ``--clone-type``): cost-vs-latency Pareto
  points from runs *pinned* at each tier (fixed per-tier step costs:
  bigger sub-meshes decode faster but bill dearer), then one **mixed**
  run — short-prompt bulk + long-context KV-hungry + a high-priority
  tenant — where the placement engine must use at least three distinct
  clone types, escalate the KV-hungry requests up the ladder
  (token-identical to the pinned-large run), and power off long-idle
  secondaries during the drain.  Deterministic (fixed-cost executor), so
  ``tools/check_bench.py`` hard-asserts all of it in CI.

    PYTHONPATH=src python benchmarks/serving_load.py
    PYTHONPATH=src python benchmarks/serving_load.py --rates 1 4 16
    PYTHONPATH=src python benchmarks/serving_load.py --kv paged --seed 3

Results are also written machine-readable to ``BENCH_serving.json`` (see
docs/benchmarks.md for the schema; ``tools/check_bench.py`` asserts it in
CI) so the perf trajectory is tracked across PRs.  All times are
virtual-clock seconds (venue-model execution + modeled transfer +
provisioning); nothing here sleeps for real.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config, reduced_config            # noqa: E402
from repro.core.clones import (CLONE_TYPES, OFF_IDLE_TTL,       # noqa: E402
                               PAUSE_IDLE_TTL, USD_PER_HOUR, CloneState)
from repro.core.policy import Policy                            # noqa: E402
from repro.core.scheduler import (ServeRequest,                 # noqa: E402
                                  poisson_arrivals)
from repro.launch.compile_cache import use_compile_cache        # noqa: E402
from repro.launch.serve import ClientHandler, LMBackend         # noqa: E402

HEADER = (f"{'rate_rps':>8s} {'kv':>10s} {'served':>6s} {'shed':>5s} "
          f"{'p50_s':>8s} {'p99_s':>8s} {'ttft50_s':>8s} "
          f"{'tok/s':>7s} {'kv_util':>7s} {'peak_2nd':>8s} "
          f"{'resumes':>7s} {'pauses':>6s} {'busy_J':>9s} "
          f"{'cost_usd':>9s}")


def run_sweep(arch: str = "smollm-360m", rates=(0.5, 4.0, 32.0),
              n_requests: int = 32, max_batch: int = 4,
              max_secondaries: int = 6, new_tokens: int = 6,
              prompt_len: int = 6, seed: int = 0,
              kv_modes=("paged", "contiguous"), block_size: int = 8,
              decode_window: int = 1, clone_type: str = "main"):
    """Returns (table_lines, rows) with one row dict per (rate, kv mode)."""
    cfg = reduced_config(get_config(arch))
    backend = LMBackend(cfg, capacity=32)
    lines = [HEADER]
    rows = []
    for rate in rates:
        for kv in kv_modes:
            # the contiguous cohort path decodes per token (the handler
            # rejects a window on it); each row records its effective window
            window = decode_window if kv == "paged" else 1
            handler = ClientHandler(backend, max_batch=max_batch,
                                    max_secondaries=max_secondaries,
                                    prompt_pad=prompt_len, kv=kv,
                                    block_size=block_size,
                                    clone_type=clone_type,
                                    decode_window=window)
            reqs = poisson_arrivals(rate, n_requests, seed=seed,
                                    prompt_len=prompt_len,
                                    vocab=cfg.vocab_size,
                                    max_new_tokens=new_tokens)
            report = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
            still_running = len(handler.pool.running_secondaries())
            lines.append(
                f"{rate:>8.2f} {kv:>10s} {len(report.completions):>6d} "
                f"{report.rejected:>5d} {report.p50_latency_s:>8.3f} "
                f"{report.p99_latency_s:>8.3f} {report.p50_ttft_s:>8.3f} "
                f"{report.tokens_per_s:>7.2f} {report.kv_util:>7.0%} "
                f"{report.peak_secondaries:>8d} "
                f"{report.pool_stats['resumes']:>7d} "
                f"{report.pool_stats['pauses']:>6d} "
                f"{report.busy_energy_j:>9.2f} "
                f"{report.cost_usd:>9.6f}")
            rows.append({
                "rate_rps": rate,
                "kv": kv,
                "decode_window": window,
                "served": len(report.completions),
                "shed": report.rejected,
                "p50_latency_s": report.p50_latency_s,
                "p99_latency_s": report.p99_latency_s,
                "p50_ttft_s": report.p50_ttft_s,
                "tokens_per_s": report.tokens_per_s,
                "kv_util": report.kv_util,
                "kv_reserved_peak_tokens": report.kv_reserved_peak,
                "prefix_hit_rate": report.prefix_hit_rate,
                "preemptions": report.preemptions,
                "restored_tokens": report.restored_tokens,
                "peak_secondaries": report.peak_secondaries,
                "resumes": report.pool_stats["resumes"],
                "boots": report.pool_stats["boots"],
                "pauses": report.pool_stats["pauses"],
                "busy_energy_j": report.busy_energy_j,
                "cost_usd": report.cost_usd,
                "escalations": report.escalations,
                "power_offs": report.power_offs,
                "makespan_s": report.makespan_s,
                "secondaries_after_drain": still_running,
                "report": report,
            })
    return lines, rows


def _p99_tpot(completions) -> float:
    """p99 time-per-output-token: decode-phase latency per token interval."""
    tpots = [(c.latency_s - c.ttft_s) / max(len(c.tokens) - 1, 1)
             for c in completions]
    return float(np.percentile(tpots, 99)) if tpots else 0.0


def run_prefix_sweep(backend, *, rate: float = 8.0, n_requests: int = 24,
                     prompt_len: int = 24, prefix_len: int = 16,
                     prefix_share: float = 0.75, new_tokens: int = 6,
                     max_batch: int = 4, block_size: int = 4,
                     num_blocks: int = 13, seed: int = 0):
    """Shared-system-prompt workload, prefix cache ON vs OFF on one trace.

    Returns one row dict per mode.  The pool is sized tight enough that
    block economics matter (admission order and preemption churn, not
    just prefill compute) — that is where the cache's TTFT/p99 win comes
    from.  Unlike the rate sweep this uses a *fixed-cost* executor (one
    venue-time unit per dispatch), so the rows isolate the scheduling
    effect deterministically: same trace + same config = same numbers,
    on any host — which is what lets ``tools/check_bench.py`` hard-assert
    the shared-vs-baseline comparison in CI."""
    rows = []
    for cached in (False, True):
        handler = ClientHandler(backend, max_batch=max_batch,
                                prompt_pad=prompt_len,
                                block_size=block_size,
                                num_blocks=num_blocks,
                                max_secondaries=2,  # concentrate the cache
                                prefix_cache=cached,
                                executor=lambda c, f, a: (f(*a), 0.05))
        reqs = poisson_arrivals(rate, n_requests, seed=seed,
                                prompt_len=prompt_len,
                                vocab=backend.cfg.vocab_size,
                                max_new_tokens=new_tokens,
                                prefix_len=prefix_len,
                                prefix_share=prefix_share)
        report = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        rows.append({
            "prefix_cache": cached,
            "prefix_len": prefix_len,
            "prefix_share": prefix_share,
            "prompt_len": prompt_len,
            "served": len(report.completions),
            "offered": n_requests,
            "shed": report.rejected,
            "p50_ttft_s": report.p50_ttft_s,
            "p50_latency_s": report.p50_latency_s,
            "p99_latency_s": report.p99_latency_s,
            "p99_tpot_s": _p99_tpot(report.completions),
            "tokens_per_s": report.tokens_per_s,
            "prefix_hit_rate": report.prefix_hit_rate,
            "preemptions": report.preemptions,
            "restored_tokens": report.restored_tokens,
            "kv_util": report.kv_util,
            "kv_reserved_peak_tokens": report.kv_reserved_peak,
        })
    return rows


def run_tight_pool_sweep(backend, *, n_requests: int = 12,
                         prompt_len: int = 8, new_tokens: int = 10,
                         max_batch: int = 4, block_size: int = 4,
                         num_blocks: int = 8, seed: int = 0):
    """Under-provisioned pool: aggregate demand far exceeds the blocks.

    Worst-case-reservation admission (the pre-ADR-003 allocator) refuses
    this concurrency outright; optimistic admission + preemption must
    complete *every* request — the row records the preemption economics
    and that zero requests failed."""
    handler = ClientHandler(backend, max_batch=max_batch,
                            prompt_pad=prompt_len, block_size=block_size,
                            num_blocks=num_blocks,
                            max_secondaries=0,   # one pool: real squeeze
                            executor=lambda c, f, a: (f(*a), 0.05))
    reqs = poisson_arrivals(50.0, n_requests, seed=seed,
                            prompt_len=prompt_len,
                            vocab=backend.cfg.vocab_size,
                            max_new_tokens=new_tokens,
                            prefix_len=prompt_len)  # all share one prompt
    runtime_errors = 0
    report = None
    try:
        report = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
    except RuntimeError:
        # recorded, not swallowed: the artifact row documents the failure
        # and tools/check_bench.py fails CI on it
        runtime_errors = 1
    blocks_needed = -(-min(prompt_len + new_tokens,
                           backend.capacity) // block_size)
    return {
        "num_blocks": num_blocks,
        "blocks_worst_case_per_request": blocks_needed,
        "offered": n_requests,
        "served": len(report.completions) if report else 0,
        "shed": report.rejected if report else 0,
        "runtime_errors": runtime_errors,
        "preemptions": handler.preemptions,
        "restored_tokens": handler.restored_tokens,
        "prefix_hit_rate": (handler.prefix_hit_tokens
                            / max(handler.prompt_tokens, 1)),
        "p50_latency_s": report.p50_latency_s if report else 0.0,
        "p99_latency_s": report.p99_latency_s if report else 0.0,
        "kv_util": report.kv_util if report else 0.0,
    }


def mixed_trace(vocab: int, *, n_cohort: int, n_join: int, prefix_len: int,
                tail_len: int, new_tokens: int, join_at, seed: int = 0):
    """Decode cohort at t=0 plus mid-stream shared-prefix joiners.

    Every prompt shares the block-aligned system prefix; each tail's
    first token is the request id, so a join diverges exactly at the
    block boundary — full prefix reuse, no copy-on-write block, which
    keeps the three serving modes' per-step cost accounting comparable.
    """
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, size=prefix_len, dtype=np.int32)
    reqs = []
    for i in range(n_cohort + n_join):
        tail = rng.integers(0, vocab, size=tail_len, dtype=np.int32)
        tail[0] = i % vocab
        arrival = 0.0 if i < n_cohort else join_at[i - n_cohort]
        reqs.append(ServeRequest(i, np.concatenate([prefix, tail]),
                                 new_tokens, arrival_t=arrival))
    return reqs


def run_mixed_dispatch_sweep(backend, *, n_cohort: int = 4, n_join: int = 2,
                             prefix_len: int = 16, tail_len: int = 8,
                             new_tokens: int = 16, window: int = 4,
                             chunk: int = 8, max_batch: int = 8,
                             block_size: int = 4, seed: int = 0):
    """Mid-stream joins vs the decode cohort's p99 TPOT (ADR-005).

    One trace, three runs: **nojoin** (cohort only, fused config — the
    TPOT floor), **serial** (joins served by a stepwise suffix-prefill
    dispatch before the decode window), **mixed** (suffix chunks fused
    into the decode window's scan).  The executor bills venue time per
    *sequential scan step* of the submitted function (``seq_steps``, set
    by the engine per dispatch), so a serial join round costs
    ``suffix_steps + window`` while a fused round costs
    ``max(window, ceil(suffix/chunk))`` — with ``suffix <= chunk *
    window`` the fused round is exactly a plain decode window, which is
    the no-stall claim ``tools/check_bench.py`` hard-asserts."""
    def executor(clone, fn, args):
        return fn(*args), 0.05 * getattr(fn, "seq_steps", 1)

    join_at = [0.45 + 0.3 * i for i in range(n_join)]

    def run(with_joins: bool, prefill_chunk: int, mixed: bool):
        handler = ClientHandler(backend, max_batch=max_batch,
                                prompt_pad=prefix_len + tail_len,
                                block_size=block_size,
                                max_secondaries=0,
                                decode_window=window,
                                prefill_chunk=prefill_chunk,
                                mixed_dispatch=mixed,
                                executor=executor)
        reqs = mixed_trace(backend.cfg.vocab_size, n_cohort=n_cohort,
                           n_join=n_join if with_joins else 0,
                           prefix_len=prefix_len, tail_len=tail_len,
                           new_tokens=new_tokens, join_at=join_at,
                           seed=seed)
        report = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        cohort = [c for c in report.completions if c.rid < n_cohort]
        row = {
            "prefill_chunk": prefill_chunk,
            "mixed_dispatch": mixed,
            "decode_window": window,
            "offered": len(reqs),
            "served": len(report.completions),
            "p50_ttft_s": report.p50_ttft_s,
            "p99_tpot_s": _p99_tpot(cohort),
            "prefix_hit_rate": report.prefix_hit_rate,
        }
        return row, {c.rid: list(map(int, c.tokens))
                     for c in report.completions}

    nojoin, _ = run(False, chunk, True)
    serial, toks_serial = run(True, 0, False)
    mixed, toks_mixed = run(True, chunk, True)
    mixed["tokens_identical_to_serial"] = toks_mixed == toks_serial
    return {"nojoin": nojoin, "serial": serial, "mixed": mixed}


FLEET_DEFAULT = ("basic", "large", "x2large")

# Deterministic per-tier venue seconds per dispatch: a bigger sub-mesh
# decodes a step faster but bills at a dearer $-rate (USD_PER_HOUR) —
# fixed, not host-measured, so CI can hard-assert the Pareto shape.
TIER_STEP_S = {"basic": 0.32, "main": 0.16, "large": 0.08,
               "x2large": 0.04, "x4large": 0.02, "x8large": 0.01}


def fleet_trace(vocab: int, *, prompt_len: int = 8, seed: int = 0):
    """Mixed workload (deterministic per seed): a high-priority tenant,
    short-prompt bulk, and long-context KV-hungry research requests."""
    rng = np.random.default_rng(seed)

    def prompt():
        return rng.integers(0, vocab, size=prompt_len, dtype=np.int32)

    reqs, rid = [], 0
    for i in range(2):            # premium tenant: urgent, short
        reqs.append(ServeRequest(rid, prompt(), 4, arrival_t=0.05 * i,
                                 priority=2, tenant="premium"))
        rid += 1
    for i in range(9):            # bulk tenant: short prompts, few tokens
        reqs.append(ServeRequest(rid, prompt(), 4, arrival_t=0.1 + 0.2 * i,
                                 tenant="bulk"))
        rid += 1
    for i in range(2):            # research tenant: KV-hungry long decodes
        reqs.append(ServeRequest(rid, prompt(), 24, arrival_t=0.2 + 0.3 * i,
                                 tenant="research"))
        rid += 1
    return reqs


def run_fleet_sweep(backend, *, fleet=FLEET_DEFAULT, seed: int = 0,
                    max_batch: int = 4, prompt_len: int = 8,
                    block_size: int = 4, num_blocks: int = 4,
                    max_secondaries: int = 6):
    """Heterogeneous fleet sweep (ADR-004).

    Pinned rows: the same trace served entirely on each tier (roomy KV)
    — the cost-vs-latency Pareto points.  Mixed row: the placement
    engine serves the trace across the fleet — bulk on the cheapest
    tier ($-policy), the high-priority tenant on the warm premium spare
    (latency-first), and the KV-hungry requests *escalated* up the
    ladder from a base-tier pool sized at ``num_blocks`` blocks; their
    tokens must be identical to the pinned-``large`` run.  The mixed
    drain runs past OFF_IDLE_TTL so the TTL power-off stage is visible
    as ``power_offs``."""
    def executor(clone, fn, args):
        return fn(*args), TIER_STEP_S[clone.ctype.name]

    def run(clone_type, fleet_types=None, premium_spare=None, nb=None,
            drain=PAUSE_IDLE_TTL + 5.0):
        handler = ClientHandler(
            backend, clone_type=clone_type,
            fleet=list(fleet_types) if fleet_types else None,
            placement_policy=Policy.NONE,   # $-aware bulk placement
            max_batch=max_batch, prompt_pad=prompt_len,
            block_size=block_size, num_blocks=nb,
            max_secondaries=max_secondaries, use_primary=False,
            executor=executor)
        if premium_spare:                   # warm hot-spare premium clone
            handler.pool.provision(premium_spare, 1,
                                   state=CloneState.RUNNING)
        reqs = fleet_trace(backend.cfg.vocab_size, prompt_len=prompt_len,
                           seed=seed)
        errors, rep = 0, None
        try:
            rep = handler.run(reqs, drain_idle_s=drain)
        except RuntimeError:
            errors = 1                      # recorded; CI fails on it
        return rep, errors, len(reqs)

    tiers = sorted(set(fleet) | {"large"},
                   key=lambda n: CLONE_TYPES[n].rank())
    pinned = {}
    rows_pinned = []
    for t in tiers:
        rep, errors, offered = run(t)
        pinned[t] = rep
        rows_pinned.append({
            "clone_type": t,
            "usd_per_hour": USD_PER_HOUR[t],
            "tier_step_s": TIER_STEP_S[t],
            "served": len(rep.completions) if rep else 0,
            "offered": offered,
            "runtime_errors": errors,
            "p50_latency_s": rep.p50_latency_s if rep else 0.0,
            "p99_latency_s": rep.p99_latency_s if rep else 0.0,
            "p50_ttft_s": rep.p50_ttft_s if rep else 0.0,
            "busy_energy_j": rep.busy_energy_j if rep else 0.0,
            "cost_usd": rep.cost_usd if rep else 0.0,
            "clone_seconds_by_type": rep.clone_seconds_by_type if rep
            else {},
        })

    base, premium = min(fleet, key=lambda n: CLONE_TYPES[n].rank()), \
        max(fleet, key=lambda n: CLONE_TYPES[n].rank())
    rep, errors, offered = run(base, fleet_types=fleet,
                               premium_spare=premium, nb=num_blocks,
                               drain=PAUSE_IDLE_TTL + OFF_IDLE_TTL + 40.0)
    ref = {c.rid: c.tokens for c in pinned["large"].completions} \
        if pinned["large"] else {}
    got = {c.rid: c.tokens for c in rep.completions} if rep else {}
    mixed = {
        "fleet": sorted(set(fleet), key=lambda n: CLONE_TYPES[n].rank()),
        "base_type": base,
        "premium_type": premium,
        "num_blocks": num_blocks,
        "served": len(got),
        "offered": offered,
        "runtime_errors": errors,
        "escalations": rep.escalations if rep else 0,
        "fleet_mix": rep.fleet_mix if rep else {},
        "distinct_types": len([t for t, n in (rep.fleet_mix if rep
                                              else {}).items() if n > 0]),
        "preemptions": rep.preemptions if rep else 0,
        "p50_latency_s": rep.p50_latency_s if rep else 0.0,
        "p99_latency_s": rep.p99_latency_s if rep else 0.0,
        "p50_ttft_s": rep.p50_ttft_s if rep else 0.0,
        "cost_usd": rep.cost_usd if rep else 0.0,
        "energy_j_by_type": rep.energy_j_by_type if rep else {},
        "clone_seconds_by_type": rep.clone_seconds_by_type if rep else {},
        "power_offs": rep.power_offs if rep else 0,
        "tokens_identical_to_pinned_large": bool(got) and got == ref,
    }
    return rows_pinned, mixed


def run_spec_sweep(backend, *, n_requests: int = 10, prompt_len: int = 7,
                   new_tokens: int = 24, max_batch: int = 4,
                   block_size: int = 4, max_secondaries: int = 3,
                   spec_k: int = 4, draft_cost: float = 0.1,
                   seed: int = 0):
    """Cross-tier speculative decoding sweep (ADR-008).

    One trace served three ways on the per-tier fixed-cost executor:
    **pinned-large** — plain per-token decode, every engine on the
    ``large`` tier (the $-per-token baseline); **cross-tier spec** — the
    same requests with speculative decoding, the draft paired on the
    fleet's cheapest tier (``basic``, billing ``draft_cost`` of a step
    per draft scan step) and ONE chunked verify dispatch per round on
    ``large``; and a **corrupted** twin whose draft proposals are
    randomly flipped, dropping acceptance below 1.0.  Every request is
    priority-1, so the urgent placement band pins serving engines to the
    fast tier — only drafts burn ``basic`` seconds.  The speculative
    rows must serve the identical token streams at a lower $-per-token
    without losing throughput — hard-asserted by ``tools/check_bench.py``
    in CI."""
    def executor(clone, fn, args):
        return fn(*args), (TIER_STEP_S[clone.ctype.name]
                           * getattr(fn, "seq_steps", 1)
                           * getattr(fn, "step_scale", 1.0))

    def trace():
        rng = np.random.default_rng(seed)
        return [ServeRequest(i, rng.integers(0, backend.cfg.vocab_size,
                                             size=prompt_len,
                                             dtype=np.int32),
                             new_tokens, arrival_t=0.15 * i, priority=1)
                for i in range(n_requests)]

    def run(scenario, speculative, corruption=0.0):
        handler = ClientHandler(
            backend, clone_type="large",
            fleet=["basic", "large"] if speculative else None,
            max_batch=max_batch, prompt_pad=prompt_len,
            block_size=block_size, max_secondaries=max_secondaries,
            use_primary=False, executor=executor,
            speculative=speculative, spec_k=spec_k,
            spec_corruption=corruption, draft_cost=draft_cost)
        errors, rep = 0, None
        try:
            rep = handler.run(trace(), drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        except RuntimeError:
            errors = 1                      # recorded; CI fails on it
        toks = {c.rid: list(map(int, c.tokens))
                for c in rep.completions} if rep else {}
        total = sum(len(t) for t in toks.values())
        return {
            "scenario": scenario,
            "speculative": speculative,
            "corruption": corruption,
            "served": len(toks),
            "offered": n_requests,
            "runtime_errors": errors,
            "total_tokens": total,
            "spec_rounds": rep.spec_rounds if rep else 0,
            "spec_tokens": rep.spec_tokens if rep else 0,
            "acceptance_rate": rep.acceptance_rate if rep else 0.0,
            "spec_fallbacks": rep.spec_fallbacks if rep else 0,
            "makespan_s": rep.makespan_s if rep else 0.0,
            "tokens_per_s": (total / rep.makespan_s
                             if rep and rep.makespan_s else 0.0),
            "cost_usd": rep.cost_usd if rep else 0.0,
            "usd_per_token": (rep.cost_usd / total
                              if rep and total else 0.0),
            "p50_ttft_s": rep.p50_ttft_s if rep else 0.0,
            "p99_latency_s": rep.p99_latency_s if rep else 0.0,
            "clone_seconds_by_type": rep.clone_seconds_by_type if rep
            else {},
        }, toks

    pinned, ref = run("pinned_large", False)
    rows = [pinned]
    for corruption in (0.0, 0.5):
        name = "spec" if corruption == 0.0 else "spec_corrupted"
        row, got = run(name, True, corruption)
        row["tokens_identical_to_pinned_large"] = bool(got) and got == ref
        rows.append(row)
    return {
        "spec_k": spec_k,
        "draft_cost": draft_cost,
        "draft_tier": "basic",
        "verify_tier": "large",
        "draft_usd_per_hour": USD_PER_HOUR["basic"],
        "verify_usd_per_hour": USD_PER_HOUR["large"],
        "rows": rows,
    }


def run_fault_sweep(backend, *, n_requests: int = 12, prompt_len: int = 8,
                    new_tokens: int = 10, max_batch: int = 4,
                    block_size: int = 4, max_secondaries: int = 3,
                    decode_window: int = 2, rate: float = 8.0,
                    seed: int = 0):
    """Fault-injected serving sweep (ADR-006).

    One Poisson trace served under escalating fault pressure, all rows
    with the same fixed-cost executor so they are deterministic and
    host-independent: a **faultless baseline**; a mid-run **drain**
    (graceful death — in-flight KV must *migrate* to a survivor); a
    mid-run **kill** (fail-stop — in-flight requests must requeue on the
    prefix-accelerated *restore* path); a **mixed** row firing one of
    each (≈10% of the fleet-seconds faulted); and a **slow** straggler
    served unhedged vs hedged.  Fault times are fractions of the
    *baseline* makespan, so the schedule stresses the mid-decode window
    regardless of trace parameters.  Every faulted row must serve every
    request with tokens bit-identical to the faultless run — recovery is
    a latency event, never a correctness event — which is exactly what
    ``tools/check_bench.py`` hard-asserts in CI."""
    from repro.core.faults import CloneFault

    def executor(clone, fn, args):
        return fn(*args), 0.05

    def run(faults=None, hedge: float = 0.0):
        handler = ClientHandler(backend, max_batch=max_batch,
                                prompt_pad=prompt_len,
                                block_size=block_size,
                                max_secondaries=max_secondaries,
                                decode_window=decode_window,
                                executor=executor, faults=faults,
                                hedge_factor=hedge, hedge_min_samples=4)
        # one warm spare in EVERY row (identical fleets keep the rows
        # comparable): hedging only races onto warm capacity — it never
        # spins up a clone for a duplicate — and recovery migration needs
        # a survivor with room
        handler.pool.provision(handler.clone_type, 1,
                               state=CloneState.RUNNING)
        reqs = poisson_arrivals(rate, n_requests, seed=seed,
                                prompt_len=prompt_len,
                                vocab=backend.cfg.vocab_size,
                                max_new_tokens=new_tokens,
                                prefix_len=prompt_len // 2)
        errors, rep = 0, None
        try:
            rep = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        except RuntimeError:
            errors = 1
        toks = ({c.rid: list(map(int, c.tokens)) for c in rep.completions}
                if rep else {})
        return rep, toks, errors

    base_rep, base_toks, base_err = run()
    span = base_rep.makespan_s if base_rep else 1.0

    def row(name, faults=None, hedge: float = 0.0):
        rep, toks, errors = run(faults=faults, hedge=hedge)
        return {
            "scenario": name,
            "faults": [{"at": f.at, "kind": f.kind, "duration": f.duration,
                        "factor": f.factor} for f in (faults or [])],
            "offered": n_requests,
            "served": len(rep.completions) if rep else 0,
            "runtime_errors": errors,
            "p50_latency_s": rep.p50_latency_s if rep else 0.0,
            "p99_latency_s": rep.p99_latency_s if rep else 0.0,
            "p50_ttft_s": rep.p50_ttft_s if rep else 0.0,
            "faults_injected": rep.faults_injected if rep else 0,
            "recoveries_migrated": rep.recoveries_migrated if rep else 0,
            "recoveries_restored": rep.recoveries_restored if rep else 0,
            "breaker_opens": rep.breaker_opens if rep else 0,
            "hedges_fired": rep.hedges_fired if rep else 0,
            "hedge_wins": rep.hedge_wins if rep else 0,
            "preemptions": rep.preemptions if rep else 0,
            "tokens_identical_to_faultless": bool(toks)
            and toks == base_toks,
        }

    # fractions tuned to the trace's busy window: at 0.5x the makespan
    # secondaries are mid-decode (a drain finds survivors with free
    # slots to migrate into), and a 0.6x straggler hits when the warm
    # spare is genuinely spare — hedging must not steal contended
    # capacity from the queue
    rows = [row("baseline")]
    rows[0]["tokens_identical_to_faultless"] = not base_err
    rows.append(row("drain", [CloneFault(at=0.5 * span, kind="drain",
                                         duration=2.0)]))
    rows.append(row("kill", [CloneFault(at=0.5 * span, kind="kill",
                                        duration=2.0)]))
    rows.append(row("mixed", [CloneFault(at=0.4 * span, kind="drain",
                                         duration=2.0),
                              CloneFault(at=0.6 * span, kind="kill",
                                         duration=2.0)]))
    slow = lambda: [CloneFault(at=0.6 * span, kind="slow",  # noqa: E731
                               duration=0.4 * span, factor=40.0)]
    rows.append(row("slow_unhedged", slow()))
    rows.append(row("slow_hedged", slow(), hedge=2.0))
    return rows


OVERLOAD_LINKS = ("wifi-local", "wifi-internet", "3g")
OVERLOAD_TENANTS = ("premium", "bulk", "research")


def overload_trace(vocab: int, *, n: int, rate: float,
                   new_tokens: int = 16, prompt_len: int = 6,
                   deadline_s: float = 3.0, seed: int = 0):
    """Multi-tenant Poisson trace for the overload sweep (ADR-007).

    Every 4th request is **interactive** (tenant ``premium``, carries an
    end-to-end deadline); the rest is deadline-less **batch** split
    between ``bulk`` and ``research`` (``research`` at lower priority —
    the shed victim ordering is observable).  Every 5th batch request
    repeats one fixed prompt so the gateway's exact-match response cache
    has real duplicates to short-circuit."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    dup_prompt = rng.integers(1, vocab, size=prompt_len).astype(np.int32)
    reqs, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        if i % 4 == 0:
            slo, deadline, tenant, prio = ("interactive", deadline_s,
                                           "premium", 2)
        else:
            slo, deadline = "batch", None
            tenant = "bulk" if i % 2 else "research"
            prio = 1 if tenant == "bulk" else 0
        prompt = (dup_prompt if (slo == "batch" and i % 5 == 0)
                  else rng.integers(1, vocab,
                                    size=prompt_len).astype(np.int32))
        reqs.append(ServeRequest(i, prompt, max_new_tokens=new_tokens,
                                 arrival_t=t, tenant=tenant, slo=slo,
                                 deadline_s=deadline, priority=prio))
    return reqs


def run_overload_sweep(backend, *, n_requests: int = 60,
                       overs=(0.4, 1.6, 3.0), new_tokens: int = 16,
                       prompt_len: int = 6, max_batch: int = 2,
                       max_secondaries: int = 1, deadline_s: float = 3.0,
                       link: str = "wifi-local", seed: int = 0):
    """Overload sweep: ungated baseline vs SLO-aware gateway (ADR-007).

    One deterministic trace per offered-load multiple (fractions of the
    fleet's token-throughput ceiling with the fixed-cost 0.05 s
    executor), each served twice: **ungated** (a practically unbounded
    admission queue — everything is accepted and waits) and **gated**
    (the :class:`~repro.core.gateway.StreamingGateway` in front).  Past
    ~1.5x capacity the ungated p99 TTFT and queue depth diverge with the
    backlog while the gateway holds interactive SLO attainment via
    class-priority release, predictive admission, and batch-only
    shedding — serving token-identical outputs for everything it admits
    (greedy decode: scheduling changes timing, never content).  A final
    pair replays the 1.6x trace with a mid-run clone **kill** (PR 7
    injector; the ``on_fire`` hook tightens admission at the fault
    instant) on a one-spare-larger fleet — graceful degradation under
    fault + overload, gated attainment above the ungated faulted
    baseline.  ``link`` selects the client link profile for both the
    handler's transfer model and the gateway's admission estimator."""
    from repro.core.faults import CloneFault
    from repro.core.gateway import StreamingGateway, TenantPolicy
    from repro.core.profilers import NetworkProfiler

    def executor(clone, fn, args):
        return fn(*args), 0.05

    # token-throughput ceiling of the faultless fleet: every clone's
    # max_batch slots emit one token per 0.05 s dispatch
    slots = max_batch * (1 + max_secondaries)
    capacity_rps = slots / (0.05 * new_tokens)

    def gateway():
        return StreamingGateway(
            tenants={"premium": TenantPolicy(weight=4.0),
                     "bulk": TenantPolicy(weight=1.0, rate=64.0, burst=64.0),
                     "research": TenantPolicy(weight=1.0, rate=64.0,
                                              burst=64.0)},
            max_backlog_tokens=8 * new_tokens, quantum=new_tokens,
            retry_base_s=0.4, retry_max=2, link=link,
            net=NetworkProfiler(link), seed=seed)

    def run(rate, gated, faults=None, secondaries=max_secondaries):
        handler = ClientHandler(
            backend, link=link, max_batch=max_batch, prompt_pad=8,
            block_size=4, max_secondaries=secondaries, decode_window=1,
            queue_depth=(2 * max_batch if gated else 100 * n_requests),
            executor=executor, gateway=gateway() if gated else None,
            faults=list(faults) if faults else None)
        reqs = overload_trace(backend.cfg.vocab_size, n=n_requests,
                              rate=rate, new_tokens=new_tokens,
                              prompt_len=prompt_len, deadline_s=deadline_s,
                              seed=seed)
        rep = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        toks = {c.rid: list(map(int, c.tokens)) for c in rep.completions}
        return rep, toks

    def row(scenario, rate, rep, toks, base_toks):
        ttfts = [c.ttft_s for c in rep.completions] or [0.0]
        return {
            "scenario": scenario,
            "rate_rps": rate,
            "over": round(rate / capacity_rps, 3),
            "gated": "gated" in scenario,
            "offered": n_requests,
            "served": len(rep.completions),
            "p50_ttft_s": float(np.percentile(ttfts, 50)),
            "p99_ttft_s": float(np.percentile(ttfts, 99)),
            "peak_queue_depth": rep.peak_queue_depth,
            "slo_attainment": dict(rep.slo_attainment),
            "goodput_tps": rep.goodput_tps,
            "shed": rep.gateway_shed,
            "shed_by_slo": dict(rep.shed_by_slo),
            "rejected": rep.gateway_rejected,
            "retries": rep.gateway_retries,
            "cache_hits": rep.cache_hits,
            "faults_injected": rep.faults_injected,
            "tokens_identical_to_ungated": all(
                base_toks.get(r) == t for r, t in toks.items()),
        }

    rows = []
    for over in overs:
        rate = round(over * capacity_rps, 3)
        rep_u, toks_u = run(rate, gated=False)
        rep_g, toks_g = run(rate, gated=True)
        rows.append(row("ungated", rate, rep_u, toks_u, toks_u))
        rows.append(row("gated", rate, rep_g, toks_g, toks_u))
    # fault + overload: replay the mid sweep point with one clone killed
    # mid-run on a one-spare-larger fleet (post-fault capacity matches
    # the faultless sweep fleet, so the comparison isolates the gateway)
    rate = round(overs[1] * capacity_rps, 3)
    faults = [CloneFault(at=1.5, kind="kill")]
    rep_u, toks_u = run(rate, gated=False, faults=faults,
                        secondaries=max_secondaries + 1)
    rep_g, toks_g = run(rate, gated=True, faults=faults,
                        secondaries=max_secondaries + 1)
    rows.append(row("fault_ungated", rate, rep_u, toks_u, toks_u))
    rows.append(row("fault_gated", rate, rep_g, toks_g, toks_u))
    return {"link": link, "capacity_rps": capacity_rps,
            "new_tokens": new_tokens, "deadline_s": deadline_s,
            "rows": rows}


def disagg_trace(vocab: int, *, n_requests: int = 16, prompt_len: int = 48,
                 new_tokens: int = 6, spacing_s: float = 0.4,
                 seed: int = 0):
    """Prefill-heavy trace: long cold prompts, short decodes, arrivals
    staggered so the shared prefill partner is never the bottleneck."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(i, rng.integers(0, vocab, size=prompt_len,
                                         dtype=np.int32),
                         new_tokens, arrival_t=spacing_s * i)
            for i in range(n_requests)]


def run_disagg_sweep(backend, *, n_requests: int = 16, prompt_len: int = 48,
                     new_tokens: int = 6, chunk: int = 8,
                     max_batch: int = 2, max_secondaries: int = 4,
                     num_blocks: int = 16, block_size: int = 8,
                     spacing_s: float = 0.4, seed: int = 0):
    """Disaggregated prefill/decode sweep (ADR-009).

    One prefill-heavy trace served four ways on the per-tier fixed-cost
    executor: **colocated_large** — every engine on the ``large`` tier
    doing its own prefills (the latency baseline disagg must match);
    **colocated_basic** — the all-cheap reference whose chunked prefills
    wreck TTFT; **disagg** — decode engines on ``basic``, cold prompts
    prefilled on ONE shared ``large`` partner clone and handed off by
    migrating the paged KV blocks over ``disagg_link``; and
    **disagg_compressed** — the same with per-block int8 KV quantization
    on the wire (~4x fewer modeled bytes).  The executor bills chunked
    partner prefills per chunk and charges the colocated one-shot
    batched prefill the same ``ceil(tokens/chunk)`` steps, so neither
    path rides free.  The compressed arm must beat colocated-large on
    $-per-token at equal-or-better p99 TTFT, and the uncompressed arm
    must serve token-identical streams — hard-asserted by
    ``tools/check_bench.py`` in CI."""
    def executor(clone, fn, args):
        steps = getattr(fn, "seq_steps", 1) * getattr(fn, "step_scale", 1.0)
        ptoks = getattr(fn, "prefill_tokens", 0)
        if ptoks:                      # colocated batched join prefill:
            steps += max(0, -(-ptoks // chunk) - 1)   # bill the chunks
        return fn(*args), TIER_STEP_S[clone.ctype.name] * steps

    def run(scenario, clone_type, disagg=False, compress=False):
        handler = ClientHandler(
            backend, clone_type=clone_type,
            fleet=["basic", "large"] if disagg else None,
            placement_policy=Policy.NONE,
            max_batch=max_batch, prompt_pad=prompt_len,
            block_size=block_size, num_blocks=num_blocks,
            max_secondaries=max_secondaries, use_primary=False,
            prefill_chunk=chunk, executor=executor,
            disagg=disagg, disagg_compress=compress,
            disagg_min_prompt=chunk if disagg else None,
            disagg_prefill_type="large" if disagg else None)
        reqs = disagg_trace(backend.cfg.vocab_size, n_requests=n_requests,
                            prompt_len=prompt_len, new_tokens=new_tokens,
                            spacing_s=spacing_s, seed=seed)
        errors, rep = 0, None
        try:
            rep = handler.run(reqs, drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        except RuntimeError:
            errors = 1                  # recorded; CI fails on it
        toks = {c.rid: list(map(int, c.tokens))
                for c in rep.completions} if rep else {}
        total = sum(len(t) for t in toks.values())
        ttfts = [c.ttft_s for c in rep.completions] \
            if rep and rep.completions else [0.0]
        return {
            "scenario": scenario,
            "clone_type": clone_type,
            "disagg": disagg,
            "compress": compress,
            "served": len(toks),
            "offered": n_requests,
            "runtime_errors": errors,
            "total_tokens": total,
            "p50_ttft_s": float(np.percentile(ttfts, 50)),
            "p99_ttft_s": float(np.percentile(ttfts, 99)),
            "makespan_s": rep.makespan_s if rep else 0.0,
            "cost_usd": rep.cost_usd if rep else 0.0,
            "usd_per_token": (rep.cost_usd / total
                              if rep and total else 0.0),
            "disagg_handoffs": rep.disagg_handoffs if rep else 0,
            "disagg_colocated": rep.disagg_colocated if rep else 0,
            "disagg_fallbacks": rep.disagg_fallbacks if rep else 0,
            "kv_transfer_bytes": rep.kv_transfer_bytes if rep else 0,
            "kv_transfer_s": rep.kv_transfer_s if rep else 0.0,
            "clone_seconds_by_type": rep.clone_seconds_by_type if rep
            else {},
        }, toks

    base, ref = run("colocated_large", "large")
    rows = [base]
    rows.append(run("colocated_basic", "basic")[0])
    for scenario, compress in (("disagg", False),
                               ("disagg_compressed", True)):
        row, got = run(scenario, "basic", disagg=True, compress=compress)
        row["tokens_identical_to_colocated_large"] = bool(got) and got == ref
        rows.append(row)
    return {
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "chunk": chunk,
        "decode_tier": "basic",
        "prefill_tier": "large",
        "decode_usd_per_hour": USD_PER_HOUR["basic"],
        "prefill_usd_per_hour": USD_PER_HOUR["large"],
        "rows": rows,
    }


def run_affinity_sweep(backend, *, families: int = 3, per_family: int = 4,
                       prefix_len: int = 16, tail_len: int = 8,
                       new_tokens: int = 4, num_blocks: int = 16,
                       block_size: int = 4, spacing_s: float = 2.5,
                       seed: int = 0):
    """Prefix-affinity routing sweep (ADR-009).

    Request families sharing a per-family system prompt, served twice on
    a homogeneous ``basic`` fleet of one single-slot engine per family:
    a near-simultaneous seeding wave pins each family's prefix into a
    distinct clone's index, then solo followers arrive with every clone
    free — each one a pure routing decision.  **affinity** routes each
    follower to the clone whose persistent prefix index holds the
    deepest match; **random** is a seeded uniform pick over the same
    candidate set.  Everything else is identical, so the global
    ``prefix_hit_rate`` isolates the routing signal: affinity must beat
    random strictly (asserted in CI).  Arrivals stay inside
    ``PAUSE_IDLE_TTL`` so the idle clones remain routable candidates."""
    def executor(clone, fn, args):
        return fn(*args), (TIER_STEP_S[clone.ctype.name]
                           * getattr(fn, "seq_steps", 1))

    prompt_len = prefix_len + tail_len
    vocab = backend.cfg.vocab_size

    def trace():
        rng = np.random.default_rng(seed)
        prefixes = [rng.integers(0, vocab, size=prefix_len, dtype=np.int32)
                    for _ in range(families)]
        reqs, rid = [], 0

        def req(fam, t):
            nonlocal rid
            tail = rng.integers(0, vocab, size=tail_len, dtype=np.int32)
            reqs.append(ServeRequest(
                rid, np.concatenate([prefixes[fam], tail]), new_tokens,
                arrival_t=t))
            rid += 1

        for fam in range(families):      # seeding wave: one engine each
            req(fam, 0.02 * fam)
        for i in range(per_family - 1):  # solo followers, all clones free
            for fam in range(families):
                req(fam, spacing_s * (1 + i * families + fam))
        return reqs

    def run(routing):
        handler = ClientHandler(
            backend, clone_type="basic", max_batch=1,
            prompt_pad=prompt_len, block_size=block_size,
            num_blocks=num_blocks, max_secondaries=families,
            use_primary=False, executor=executor, routing=routing)
        errors, rep = 0, None
        try:
            rep = handler.run(trace(), drain_idle_s=PAUSE_IDLE_TTL + 5.0)
        except RuntimeError:
            errors = 1                  # recorded; CI fails on it
        return {
            "scenario": routing,
            "served": len(rep.completions) if rep else 0,
            "offered": families * per_family,
            "runtime_errors": errors,
            "prefix_hit_rate": rep.prefix_hit_rate if rep else 0.0,
            "p50_ttft_s": rep.p50_ttft_s if rep else 0.0,
            "per_clone": rep.per_clone if rep else {},
        }

    return {
        "families": families,
        "per_family": per_family,
        "prefix_len": prefix_len,
        "prompt_len": prompt_len,
        "rows": [run("affinity"), run("random")],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--rates", type=float, nargs="+",
                    default=[0.5, 4.0, 32.0])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--secondaries", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-trace seed (deterministic per seed)")
    ap.add_argument("--kv", choices=["both", "paged", "contiguous"],
                    default="both")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--window", type=int, default=1,
                    help="paged decode window: tokens fused per dispatch")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="shared system-prompt length for the prefix "
                         "sweep (0 disables the sweep)")
    ap.add_argument("--prefix-share", type=float, default=0.75,
                    help="fraction of prefix-sweep requests sharing the "
                         "system prompt")
    ap.add_argument("--tight-blocks", type=int, default=8,
                    help="pool size for the tight-pool preemption sweep "
                         "(0 disables the sweep)")
    ap.add_argument("--mixed-joins", type=int, default=2,
                    help="mid-stream joiners for the mixed-dispatch sweep "
                         "(0 disables the sweep)")
    ap.add_argument("--clone-type", default="main",
                    choices=sorted(CLONE_TYPES),
                    help="clone type the rate sweep's handler is pinned at")
    ap.add_argument("--fleet", nargs="*", default=None,
                    metavar="TYPE", choices=sorted(CLONE_TYPES),
                    help="clone types for the heterogeneous fleet sweep "
                         f"(default: {' '.join(FLEET_DEFAULT)}; pass an "
                         "empty list to disable the sweep)")
    ap.add_argument("--fault-requests", type=int, default=12,
                    help="requests for the fault-injection sweep "
                         "(0 disables the sweep)")
    ap.add_argument("--link", default="wifi-local",
                    choices=OVERLOAD_LINKS,
                    help="client link profile (core/venues.py::LINKS) for "
                         "the overload sweep's transfer model + the "
                         "gateway's link-aware admission estimator")
    ap.add_argument("--overload-requests", type=int, default=60,
                    help="requests per overload-sweep run "
                         "(0 disables the sweep)")
    ap.add_argument("--spec-requests", type=int, default=10,
                    help="requests for the cross-tier speculative "
                         "decoding sweep (0 disables the sweep)")
    ap.add_argument("--draft-cost", type=float, default=0.1,
                    help="modeled draft step cost as a fraction of a "
                         "full step for the speculative sweep (the smoke "
                         "model's own parameter ratio is "
                         "embedding-dominated)")
    ap.add_argument("--disagg-requests", type=int, default=16,
                    help="requests for the disaggregated prefill/decode "
                         "sweep (0 disables the sweep + the routing "
                         "sub-sweep)")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable output path ('' to skip)")
    args = ap.parse_args()
    use_compile_cache()

    modes = (("paged", "contiguous") if args.kv == "both" else (args.kv,))
    lines, rows = run_sweep(args.arch, tuple(args.rates), args.requests,
                            args.batch, args.secondaries, args.new_tokens,
                            seed=args.seed, kv_modes=modes,
                            block_size=args.block_size,
                            decode_window=args.window,
                            clone_type=args.clone_type)
    print("\n".join(lines))

    # highest offered rate regardless of CLI order; among its modes take
    # the most elastic row for the provisioning assertion
    hi_rate = max(args.rates)
    hi = max((r for r in rows if r["rate_rps"] == hi_rate),
             key=lambda r: r["peak_secondaries"])
    hi_rep = hi["report"]
    print(f"\nhigh load ({hi_rate} req/s, {hi['kv']}): autoscaler peaked at "
          f"{hi_rep.peak_secondaries} secondaries "
          f"({hi['resumes']} resumes, {hi['boots']} boots); after the idle "
          f"drain {hi['secondaries_after_drain']} remain running "
          f"({hi['pauses']} TTL pauses).")
    # acceptance check — only meaningful when the offered load is actually
    # high and the cap allows elasticity; a decode window > 1 legitimately
    # absorbs the same load on fewer clones (fewer dispatch round-trips per
    # token), so the elasticity floor only applies to per-token dispatch
    if args.secondaries >= 2 and hi_rate >= 2.0 and args.requests >= 8 \
            and args.window == 1:
        assert hi_rep.peak_secondaries >= 2, \
            "autoscaler failed to provision secondaries under high load"
    assert all(r["secondaries_after_drain"] == 0 for r in rows), \
        "idle TTL failed to pause the secondaries"
    lo_rate = min(args.rates)
    lo = next(r for r in rows if r["rate_rps"] == lo_rate
              and r["kv"] == hi["kv"])           # same mode: rate effect only
    print(f"latency under load ({hi['kv']}): p99 {lo['p99_latency_s']:.3f}s "
          f"@ {lo_rate} req/s -> {hi['p99_latency_s']:.3f}s @ {hi_rate} "
          f"req/s")
    if len(modes) == 2:
        for rate in args.rates:
            pr = next(r for r in rows if r["rate_rps"] == rate
                      and r["kv"] == "paged")
            cr = next(r for r in rows if r["rate_rps"] == rate
                      and r["kv"] == "contiguous")
            print(f"paged vs contiguous @ {rate} req/s: "
                  f"ttft50 {pr['p50_ttft_s']:.3f}s vs "
                  f"{cr['p50_ttft_s']:.3f}s, "
                  f"p99 {pr['p99_latency_s']:.3f}s vs "
                  f"{cr['p99_latency_s']:.3f}s, "
                  f"kv_util {pr['kv_util']:.0%} vs {cr['kv_util']:.0%}")

    # --- ADR-003 sweeps: shared-prefix cache + tight-pool preemption ----
    cfg = reduced_config(get_config(args.arch))
    sweep_backend = LMBackend(cfg, capacity=32)
    prefix_rows = []
    if args.prefix_len > 0:
        prefix_rows = run_prefix_sweep(
            sweep_backend, prefix_len=args.prefix_len,
            prefix_share=args.prefix_share, seed=args.seed)
        base, shared = prefix_rows
        print(f"\nshared prefix ({args.prefix_len} of "
              f"{shared['prompt_len']} tokens, "
              f"{args.prefix_share:.0%} of requests): "
              f"hit_rate {shared['prefix_hit_rate']:.0%} "
              f"(baseline {base['prefix_hit_rate']:.0%}), "
              f"ttft50 {shared['p50_ttft_s']:.3f}s vs "
              f"{base['p50_ttft_s']:.3f}s, p99 "
              f"{shared['p99_latency_s']:.3f}s vs "
              f"{base['p99_latency_s']:.3f}s, preemptions "
              f"{shared['preemptions']} vs {base['preemptions']}")
        assert shared["prefix_hit_rate"] > 0.0, \
            "shared-prefix sweep produced no prefix hits"
        assert shared["served"] == base["served"] == shared["offered"]
        assert shared["p50_ttft_s"] <= base["p50_ttft_s"], \
            "prefix sharing must not raise TTFT (deterministic sweep)"
    tight_row = None
    if args.tight_blocks > 0:
        tight_row = run_tight_pool_sweep(
            sweep_backend, num_blocks=args.tight_blocks, seed=args.seed)
        print(f"tight pool ({tight_row['num_blocks'] - 1} real blocks, "
              f"{tight_row['blocks_worst_case_per_request']} worst-case "
              f"per request x {tight_row['offered']} requests): "
              f"served {tight_row['served']}/{tight_row['offered']} with "
              f"{tight_row['preemptions']} preemptions, "
              f"{tight_row['restored_tokens']} restored tokens, "
              f"0 RuntimeErrors")
        assert tight_row["runtime_errors"] == 0, \
            "tight pool must preempt, never crash"
        assert tight_row["served"] == tight_row["offered"], \
            "tight-pool sweep shed or lost requests"
        assert tight_row["preemptions"] > 0, \
            "tight-pool sweep never preempted: pool not actually tight"

    # --- ADR-005 sweep: mixed prefill/decode dispatch under joins -------
    mixed_payload = None
    if args.mixed_joins > 0:
        # roomy capacity: the sweep decodes past the rate-sweep backend's
        # 32-token ceiling (24-token prompts + 16 new tokens)
        mixed_payload = run_mixed_dispatch_sweep(
            LMBackend(cfg, capacity=64), n_join=args.mixed_joins,
            seed=args.seed)
        nj, se, mx = (mixed_payload[k] for k in ("nojoin", "serial",
                                                 "mixed"))
        print(f"\nmixed dispatch ({args.mixed_joins} mid-stream joins): "
              f"cohort p99 TPOT {mx['p99_tpot_s']:.3f}s fused vs "
              f"{se['p99_tpot_s']:.3f}s serial "
              f"(no-join baseline {nj['p99_tpot_s']:.3f}s), served "
              f"{mx['served']}/{mx['offered']}, tokens identical to "
              f"serial: {mx['tokens_identical_to_serial']}")
        for name, row in mixed_payload.items():
            assert row["served"] == row["offered"], \
                f"mixed-dispatch sweep ({name}) shed or lost requests"
        # epsilon: a join re-uploads the grown block table, whose modeled
        # transfer time (~1e-5 s) the no-join baseline never pays; the
        # serial stall it must discriminate is one scan step (0.05 s)
        assert mx["p99_tpot_s"] <= nj["p99_tpot_s"] + 1e-4, \
            "mid-stream joins stalled the decode cohort under mixed dispatch"
        assert se["p99_tpot_s"] > nj["p99_tpot_s"] + 1e-4, \
            "serial prefill-then-decode shows no stall: sweep not binding"
        assert mx["tokens_identical_to_serial"], \
            "mixed dispatch diverged from the serial path"

    # --- ADR-004 sweep: heterogeneous fleet placement + escalation ------
    fleet = FLEET_DEFAULT if args.fleet is None else tuple(args.fleet)
    fleet_payload = None
    if fleet:
        rows_pinned, mixed = run_fleet_sweep(sweep_backend, fleet=fleet,
                                             seed=args.seed)
        fleet_payload = {"pinned": rows_pinned, "mixed": mixed}
        print("\nfleet Pareto (pinned tiers, fixed-cost executor):")
        for r in rows_pinned:
            print(f"  {r['clone_type']:>8s} ${r['usd_per_hour']:.3f}/h "
                  f"p50={r['p50_latency_s']:.3f}s "
                  f"p99={r['p99_latency_s']:.3f}s "
                  f"cost=${r['cost_usd']:.6f} "
                  f"busy={r['busy_energy_j']:.0f}J")
        mix_str = " ".join(f"{t}:{n}" for t, n in
                           sorted(mixed["fleet_mix"].items()))
        print(f"mixed fleet run: served {mixed['served']}/"
              f"{mixed['offered']} across [{mix_str}] with "
              f"{mixed['escalations']} escalations, "
              f"cost=${mixed['cost_usd']:.6f}, "
              f"{mixed['power_offs']} TTL power-offs, tokens identical to "
              f"pinned-large: {mixed['tokens_identical_to_pinned_large']}")
        assert mixed["runtime_errors"] == 0, \
            "mixed fleet run raised — escalation must absorb KV pressure"
        assert mixed["served"] == mixed["offered"], \
            "mixed fleet run shed or lost requests"
        assert mixed["distinct_types"] >= 3, \
            "placement engine used fewer than three clone types"
        assert mixed["escalations"] >= 1, \
            "no KV-hungry request was escalated up the ladder"
        assert mixed["tokens_identical_to_pinned_large"], \
            "escalated serving diverged from the pinned-large run"
        assert mixed["power_offs"] >= 1, \
            "OFF_IDLE_TTL never powered off an idle secondary in the drain"

    # --- ADR-006 sweep: fault injection, recovery, hedging --------------
    fault_rows = None
    if args.fault_requests > 0:
        fault_rows = run_fault_sweep(sweep_backend,
                                     n_requests=args.fault_requests,
                                     seed=args.seed)
        by = {r["scenario"]: r for r in fault_rows}
        print("\nfault sweep (fixed-cost executor, faults at fractions of "
              "the faultless makespan):")
        for r in fault_rows:
            print(f"  {r['scenario']:>13s} served {r['served']:>2d}/"
                  f"{r['offered']} p99={r['p99_latency_s']:.3f}s "
                  f"inj={r['faults_injected']} "
                  f"mig={r['recoveries_migrated']} "
                  f"rest={r['recoveries_restored']} "
                  f"breaker={r['breaker_opens']} "
                  f"hedge={r['hedges_fired']}/{r['hedge_wins']} "
                  f"identical={r['tokens_identical_to_faultless']}")
        for r in fault_rows:
            assert r["runtime_errors"] == 0, \
                f"fault sweep ({r['scenario']}) raised: recovery must " \
                "absorb clone death"
            assert r["served"] == r["offered"], \
                f"fault sweep ({r['scenario']}) lost requests"
            assert r["tokens_identical_to_faultless"], \
                f"fault sweep ({r['scenario']}) diverged from the " \
                "faultless run"
        assert by["drain"]["recoveries_migrated"] >= 1, \
            "drain fault never migrated KV to a survivor"
        assert by["kill"]["recoveries_restored"] >= 1, \
            "kill fault never restored a request"
        assert by["slow_hedged"]["hedge_wins"] >= 1, \
            "hedged run never won a straggler race"
        assert (by["slow_hedged"]["p99_latency_s"]
                <= by["slow_unhedged"]["p99_latency_s"] + 1e-9), \
            "hedging failed to bound the straggler's p99"

    # --- ADR-007 sweep: overload, gated vs ungated ----------------------
    overload_payload = None
    if args.overload_requests > 0:
        overload_payload = run_overload_sweep(
            sweep_backend, n_requests=args.overload_requests,
            link=args.link, seed=args.seed)
        cap = overload_payload["capacity_rps"]
        print(f"\noverload sweep (link={args.link}, capacity "
              f"~{cap:.1f} req/s, fixed-cost executor):")
        for r in overload_payload["rows"]:
            slo_i = r["slo_attainment"].get("interactive", 1.0)
            print(f"  {r['scenario']:>13s} {r['over']:.1f}x "
                  f"served {r['served']:>2d}/{r['offered']} "
                  f"p99_ttft={r['p99_ttft_s']:.2f}s "
                  f"peakq={r['peak_queue_depth']:>3d} "
                  f"slo_i={slo_i:.2f} good={r['goodput_tps']:.0f}tok/s "
                  f"shed={r['shed']} rej={r['rejected']} "
                  f"cache={r['cache_hits']} retries={r['retries']} "
                  f"identical={r['tokens_identical_to_ungated']}")
        by = {(r["scenario"], r["over"]): r
              for r in overload_payload["rows"]}
        ungated = sorted((r for r in overload_payload["rows"]
                          if r["scenario"] == "ungated"),
                         key=lambda r: r["over"])
        # baseline divergence: p99 TTFT and queue depth grow with load
        for lo, hi_r in zip(ungated, ungated[1:]):
            assert hi_r["p99_ttft_s"] > 1.3 * lo["p99_ttft_s"], \
                "ungated p99 TTFT did not diverge with offered load"
            assert hi_r["peak_queue_depth"] > lo["peak_queue_depth"], \
                "ungated queue depth did not grow with offered load"
        for r in overload_payload["rows"]:
            if not r["gated"]:
                continue
            assert "interactive" not in r["shed_by_slo"], \
                f"gateway shed interactive work ({r['scenario']})"
            assert r["tokens_identical_to_ungated"], \
                f"gated run diverged from ungated tokens ({r['scenario']})"
            if r["scenario"] == "gated":
                assert r["cache_hits"] >= 1, \
                    "response cache never short-circuited a duplicate"
            if r["scenario"] == "gated" and r["over"] >= 1.5:
                assert r["slo_attainment"].get("interactive", 0) >= 0.95, \
                    f"gateway lost the interactive SLO at {r['over']}x"
                twin = by[("ungated", r["over"])]
                assert r["goodput_tps"] >= twin["goodput_tps"], \
                    f"gating lost goodput at {r['over']}x overload"
        fg, fu = by[("fault_gated", ungated[1]["over"])], \
            by[("fault_ungated", ungated[1]["over"])]
        assert (fg["slo_attainment"].get("interactive", 0)
                >= fu["slo_attainment"].get("interactive", 1) + 0.15), \
            "fault+overload: gateway not above the ungated faulted baseline"

    # --- ADR-008 sweep: cross-tier speculative decoding -----------------
    spec_payload = None
    if args.spec_requests > 0:
        spec_payload = run_spec_sweep(
            LMBackend(cfg, capacity=32, draft="oracle"),
            n_requests=args.spec_requests, draft_cost=args.draft_cost,
            seed=args.seed)
        by = {r["scenario"]: r for r in spec_payload["rows"]}
        print(f"\nspeculative sweep (K={spec_payload['spec_k']}, draft on "
              f"{spec_payload['draft_tier']} @ {args.draft_cost:.2f}x step "
              f"cost, verify on {spec_payload['verify_tier']}):")
        for r in spec_payload["rows"]:
            ident = r.get("tokens_identical_to_pinned_large", "-")
            print(f"  {r['scenario']:>14s} served {r['served']:>2d}/"
                  f"{r['offered']} accept={r['acceptance_rate']:.2f} "
                  f"rounds={r['spec_rounds']} "
                  f"fallbacks={r['spec_fallbacks']} "
                  f"{r['tokens_per_s']:.2f}tok/s "
                  f"${r['usd_per_token'] * 1e6:.2f}/Mtok "
                  f"identical={ident}")
        for r in spec_payload["rows"]:
            assert r["runtime_errors"] == 0, \
                f"spec sweep ({r['scenario']}) raised"
            assert r["served"] == r["offered"], \
                f"spec sweep ({r['scenario']}) shed or lost requests"
            if r["speculative"]:
                assert r["tokens_identical_to_pinned_large"], \
                    f"spec sweep ({r['scenario']}) diverged from plain " \
                    "greedy decode"
        assert by["spec"]["acceptance_rate"] == 1.0, \
            "oracle draft did not reach full acceptance"
        assert 0.0 < by["spec_corrupted"]["acceptance_rate"] < 1.0, \
            "corrupted draft acceptance not in (0, 1): sweep not binding"
        assert by["spec"]["usd_per_token"] < by["pinned_large"][
            "usd_per_token"], \
            "speculation failed to cut $-per-token vs pinned-large"
        assert by["spec"]["tokens_per_s"] >= by["pinned_large"][
            "tokens_per_s"], \
            "speculation lost throughput vs pinned-large"

    # --- ADR-009 sweep: disaggregated prefill/decode + routing ----------
    disagg_payload = None
    if args.disagg_requests > 0:
        disagg_payload = run_disagg_sweep(LMBackend(cfg, capacity=64),
                                          n_requests=args.disagg_requests,
                                          seed=args.seed)
        disagg_payload["affinity"] = run_affinity_sweep(sweep_backend,
                                                        seed=args.seed)
        by = {r["scenario"]: r for r in disagg_payload["rows"]}
        print(f"\ndisagg sweep (prompt {disagg_payload['prompt_len']} tok, "
              f"decode {disagg_payload['new_tokens']} tok, decode on "
              f"{disagg_payload['decode_tier']}, shared prefill partner "
              f"on {disagg_payload['prefill_tier']}):")
        for r in disagg_payload["rows"]:
            ident = r.get("tokens_identical_to_colocated_large", "-")
            print(f"  {r['scenario']:>17s} served {r['served']:>2d}/"
                  f"{r['offered']} ttft p50={r['p50_ttft_s']:.3f}s "
                  f"p99={r['p99_ttft_s']:.3f}s "
                  f"${r['usd_per_token'] * 1e6:.2f}/Mtok "
                  f"handoffs={r['disagg_handoffs']} "
                  f"xfer={r['kv_transfer_bytes']}B "
                  f"identical={ident}")
        for r in disagg_payload["rows"]:
            assert r["runtime_errors"] == 0, \
                f"disagg sweep ({r['scenario']}) raised"
            assert r["served"] == r["offered"], \
                f"disagg sweep ({r['scenario']}) shed or lost requests"
            if r["disagg"]:
                assert r["disagg_handoffs"] >= 1, \
                    f"disagg sweep ({r['scenario']}) never handed off"
        assert by["disagg"]["tokens_identical_to_colocated_large"], \
            "uncompressed disagg handoff diverged from colocated decode"
        assert by["disagg_compressed"]["kv_transfer_bytes"] \
            < 0.5 * by["disagg"]["kv_transfer_bytes"], \
            "int8 KV compression saved < 2x on modeled transfer bytes"
        assert by["disagg_compressed"]["usd_per_token"] \
            < by["colocated_large"]["usd_per_token"], \
            "disagg+compressed failed to cut $-per-token vs colocated-large"
        assert by["disagg_compressed"]["p99_ttft_s"] \
            <= by["colocated_large"]["p99_ttft_s"] + 1e-9, \
            "disagg+compressed lost p99 TTFT vs colocated-large"
        aff = {r["scenario"]: r
               for r in disagg_payload["affinity"]["rows"]}
        print(f"prefix-affinity routing "
              f"({disagg_payload['affinity']['families']} families x "
              f"{disagg_payload['affinity']['per_family']}, "
              f"{disagg_payload['affinity']['prefix_len']} of "
              f"{disagg_payload['affinity']['prompt_len']} tokens shared): "
              f"hit_rate {aff['affinity']['prefix_hit_rate']:.0%} affinity "
              f"vs {aff['random']['prefix_hit_rate']:.0%} random")
        for r in aff.values():
            assert r["runtime_errors"] == 0, \
                f"affinity sweep ({r['scenario']}) raised"
            assert r["served"] == r["offered"], \
                f"affinity sweep ({r['scenario']}) shed or lost requests"
        assert aff["affinity"]["prefix_hit_rate"] \
            > aff["random"]["prefix_hit_rate"], \
            "prefix-affinity routing did not beat random placement"

    if args.json:
        payload = {
            "benchmark": "serving_load",
            "arch": args.arch,
            "seed": args.seed,
            "requests": args.requests,
            "max_batch": args.batch,
            "max_secondaries": args.secondaries,
            "new_tokens": args.new_tokens,
            "block_size": args.block_size,
            "decode_window": args.window,
            "clone_type": args.clone_type,
            "rows": [{k: v for k, v in r.items() if k != "report"}
                     for r in rows],
            "prefix_sweep": prefix_rows,
            "tight_pool": tight_row,
            "fleet_sweep": fleet_payload,
            "mixed_dispatch": mixed_payload,
            "fault_sweep": fault_rows,
            "link": args.link,
            "overload_sweep": overload_payload,
            "spec": spec_payload,
            "disagg": disagg_payload,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
