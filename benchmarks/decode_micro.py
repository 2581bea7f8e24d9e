"""Decode hot-path micro-benchmark: fused kernel + on-device decode window.

Measures the two layers of the flash-decoding fast path *directly*, instead
of through the virtual-clock serving simulation:

1. **Kernel sweep** — ``ops.paged_attention`` fused (grid ``(B, Hkv, M)``,
   one KV block fetch per GQA group) vs unfused (grid ``(B, Hq, M)``, one
   fetch per query head), swept over GQA group sizes and block sizes.
   Reports wall time per call and the exact KV-block fetch counts from the
   kernel grids (``flash_attention.paged_kv_fetches``) — the fused kernel
   must stage each block once per group, i.e. g x fewer fetches.

2. **Decode loop** — ``model.decode_loop`` (one ``lax.scan`` dispatch per
   T-token window) vs T calls of the per-token ``decode_slots`` path on the
   same paged pool, with mid-window completions exercised via ragged
   ``steps_left``.  Reports dispatches/token, wall time per token, and
   verifies token-identical output (the equivalence the serving layer
   relies on).

3. **Prefill loop** — chunked ``model.prefill_chunks`` (ADR-005: C suffix
   tokens per sequential step through the paged chunk kernel) vs the
   stepwise ``model.prefill_loop`` scan (one token per step) on the same
   staged prefix.  Reports sequential steps per suffix token, prefill
   tokens/s, and verifies token identity: bitwise-equal first tokens *and*
   a bitwise-equal decode-window continuation on both result pools (the
   continuation reads every block the prefill wrote, so it catches any
   KV-scatter divergence, not just logit agreement at the last position).

    PYTHONPATH=src python benchmarks/decode_micro.py            # TPU
    PYTHONPATH=src python benchmarks/decode_micro.py --smoke --interpret

Results are written machine-readable to ``BENCH_decode.json`` (schema
asserted by ``tools/check_bench.py``; metric glossary in
docs/benchmarks.md).  On a TPU the kernel sweep times the compiled Mosaic
kernels.  On the CPU the kernels run only in the Pallas interpreter, and
only when ``--interpret`` asks for it: absolute microseconds then measure
Python/XLA dispatch overhead rather than MXU throughput, and the fetch and
dispatch counts are the hardware-independent claims.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402

from repro.configs import get_config, reduced_config            # noqa: E402
from repro.kernels import ops                                   # noqa: E402
from repro.kernels.flash_attention import paged_kv_fetches      # noqa: E402
from repro.launch.compile_cache import use_compile_cache        # noqa: E402
from repro.launch.serve import KVBlockPool, LMBackend           # noqa: E402


def _time_call(fn, reps: int) -> float:
    """Median wall time per call in microseconds (fn is warm)."""
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples) * 1e6)


# --------------------------------------------------------------------------- #
# 1. kernel sweep: fused vs per-head paged attention
# --------------------------------------------------------------------------- #
def kernel_sweep(cases, *, b: int, ctx_blocks: int, d: int, reps: int,
                 interpret: bool):
    rows = []
    key = jax.random.PRNGKey(0)
    for hq, hkv, bs in cases:
        g = hq // hkv
        m = ctx_blocks
        n_blocks = b * m + 1                        # block 0 = trash
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (b, 1, hq, d), jnp.float32)
        kp = jax.random.normal(k2, (n_blocks, hkv, bs, d), jnp.float32)
        vp = jax.random.normal(k3, (n_blocks, hkv, bs, d), jnp.float32)
        tables = jnp.asarray(
            1 + np.arange(b * m, dtype=np.int32).reshape(b, m))
        lens = jnp.full((b,), m * bs, jnp.int32)

        def call(fused):
            return ops.paged_attention(q, kp, vp, tables, lens,
                                       fused=fused, interpret=interpret)

        out_f = jax.block_until_ready(call(True))           # warm + compile
        out_u = jax.block_until_ready(call(False))
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                                   atol=2e-5, rtol=2e-5)
        row = {
            "b": b, "hq": hq, "hkv": hkv, "group": g, "block_size": bs,
            "num_blocks": n_blocks, "ctx_tokens": m * bs,
            "fused_us": _time_call(lambda: call(True), reps),
            "unfused_us": _time_call(lambda: call(False), reps),
            "kv_fetches_fused": paged_kv_fetches(b, hq, hkv, m, fused=True),
            "kv_fetches_unfused": paged_kv_fetches(b, hq, hkv, m,
                                                   fused=False),
            "fetch_ratio": g,
        }
        rows.append(row)
        print(f"  kernel Hq={hq} Hkv={hkv} bs={bs}: "
              f"fused={row['fused_us']:.0f}us unfused={row['unfused_us']:.0f}us "
              f"fetches {row['kv_fetches_fused']} vs "
              f"{row['kv_fetches_unfused']} ({g}x)")
    return rows


# --------------------------------------------------------------------------- #
# 2. decode loop: one dispatch per T-token window vs T per-token dispatches
# --------------------------------------------------------------------------- #
BLOCK_SIZE = 8


def _paged_setup(backend, slots: int, prompt_len: int, budgets):
    """Prefill ``slots`` prompts into a fresh pool; returns (kv, first_tok)."""
    kv = KVBlockPool(backend, slots, BLOCK_SIZE)
    prefill = backend.paged_fns(kv.bs)[0]
    rng = np.random.default_rng(0)
    toks = rng.integers(0, backend.cfg.vocab_size, (slots, prompt_len),
                        dtype=np.int32)
    joins = [kv.alloc_slot(prompt_len, int(bu)) for bu in budgets]
    blks = jnp.stack([jnp.asarray(b_) for _, b_, _, _ in joins])
    slot_ids = jnp.asarray([s for s, _, _, _ in joins], jnp.int32)
    firsts, pool = prefill(backend.params, jnp.asarray(toks), kv.pool,
                           blks, slot_ids)
    kv.pool = pool
    kv.active[:slots] = True
    return kv, np.asarray(firsts, np.int32)


def decode_loop_bench(arch: str, *, slots: int, window: int, prompt_len: int,
                      reps: int, donate: bool):
    cfg = reduced_config(get_config(arch))
    backend = LMBackend(cfg, capacity=64)
    # ragged budgets: some rows finish mid-window (trash-block parking)
    budgets = np.array([window] * (slots // 2)
                       + [max(1, window // 2)] * (slots - slots // 2),
                       np.int32)

    # --- per-token reference path (PR-2 hot loop: 1 dispatch / token) --
    kv, tok = _paged_setup(backend, slots, prompt_len, budgets)
    decode_slots = backend.paged_fns(kv.bs)[1]
    # warm (compile) on a throwaway pool copy so the timed loop is steady-state
    jax.block_until_ready(decode_slots(
        backend.params, jax.tree.map(jnp.copy, kv.pool),
        jnp.asarray(tok[:, None]), jnp.asarray(kv.pos),
        jnp.asarray(kv.tables)))
    ref_out = np.zeros((slots, window), np.int32)
    dispatches_ref = 0
    t0 = time.perf_counter()
    cur = tok.copy()
    for t in range(window):
        live = (t < budgets)
        kv.active[:] = live                 # retired rows stop growing
        kv.grow_for_write()                 # one-token lookahead (PR-2)
        eff = np.where(live, np.minimum(kv.pos, backend.capacity - 1), 0)
        tables = np.where(live[:, None], kv.tables, 0)
        nxt, kv.pool = decode_slots(
            backend.params, kv.pool, jnp.asarray(cur[:, None]),
            jnp.asarray(eff), jnp.asarray(tables))
        dispatches_ref += 1
        nxt = np.asarray(nxt, np.int32)
        cur = np.where(live, nxt, cur)
        ref_out[:, t] = cur
        kv.pos[:] = np.where(live, np.minimum(kv.pos + 1, kv.capacity),
                             kv.pos)
    stepwise_s = time.perf_counter() - t0
    tokens_total = int(budgets.sum())

    # --- fused window path (1 dispatch / T-token window) ---------------
    kv2, tok2 = _paged_setup(backend, slots, prompt_len, budgets)
    decode_window = backend.paged_fns(kv2.bs, window=window,
                                      donate=donate)[2]
    kv2.grow_for_window(budgets)             # whole window pre-reserved
    pool0 = kv2.pool
    rest = (jnp.asarray(tok2[:, None]),
            jnp.asarray(np.minimum(kv2.pos, backend.capacity - 1)),
            jnp.asarray(budgets), jnp.asarray(kv2.tables))

    def run_window():
        # a donated pool is consumed by the call: each run gets a copy
        pool_i = jax.tree.map(jnp.copy, pool0) if donate else pool0
        jax.block_until_ready(pool_i)
        t0 = time.perf_counter()
        out = jax.block_until_ready(decode_window(backend.params, pool_i,
                                                  *rest))
        return out, time.perf_counter() - t0

    (win_out, _), _ = run_window()           # compile + verify
    dispatches_win = 1
    win_out = np.asarray(win_out, np.int32)
    t_win = [run_window()[1] for _ in range(reps)]

    # per-token path emits `cur` frozen after a row's budget, as does the
    # window path — compare the full (slots, window) grids
    tokens_match = bool((ref_out == win_out).all())
    row = {
        "window": window,
        "slots": slots,
        "tokens_emitted": tokens_total,
        "dispatches_per_token": dispatches_win / tokens_total,
        "dispatches_per_token_stepwise": dispatches_ref / tokens_total,
        "us_per_token": float(np.median(t_win) * 1e6 / tokens_total),
        "us_per_token_stepwise": stepwise_s * 1e6 / tokens_total,
        "pool_donated": donate,
        "tokens_match": tokens_match,
    }
    print(f"  loop T={window} donate={donate}: "
          f"{row['dispatches_per_token']:.3f} vs "
          f"{row['dispatches_per_token_stepwise']:.3f} dispatches/token, "
          f"{row['us_per_token']:.0f} vs {row['us_per_token_stepwise']:.0f} "
          f"us/token, match={tokens_match}")
    return row


# --------------------------------------------------------------------------- #
# 3. prefill loop: C tokens per chunk step vs one token per stepwise step
# --------------------------------------------------------------------------- #
def prefill_bench(arch: str, *, rows: int, prefix_len: int, suffix_len: int,
                  chunk: int, reps: int):
    """Chunked vs stepwise paged suffix prefill over a staged prefix.

    Both paths consume the identical suffix batch on the identical pool
    (prefix already resident), so the A/B isolates the scan granularity:
    ``suffix_len`` sequential steps (stepwise) vs ``ceil(suffix_len/chunk)``
    (chunked).  ``dispatches_per_token`` counts those sequential kernel
    steps per emitted suffix token — the hardware-independent claim; wall
    time on this CPU container measures interpret-mode dispatch overhead.
    """
    assert prefix_len % BLOCK_SIZE == 0, "staged prefix must be block-aligned"
    cfg = reduced_config(get_config(arch))
    backend = LMBackend(cfg, capacity=64)
    rng = np.random.default_rng(1)
    total = prefix_len + suffix_len
    cont = 4                                  # decode continuation window

    # stage the prefix: claim slots for the full prompt, prefill the prefix
    # blocks only — the suffix blocks are allocated but still unwritten
    kv = KVBlockPool(backend, rows, BLOCK_SIZE)
    prefill_into = backend.paged_fns(kv.bs)[0]
    joins = [kv.alloc_slot(total, 1) for _ in range(rows)]
    slot_ids = np.asarray([s for s, _, _, _ in joins], np.int32)
    nb_pre = prefix_len // BLOCK_SIZE
    pre = rng.integers(0, cfg.vocab_size, (rows, prefix_len), dtype=np.int32)
    blks = jnp.stack([jnp.asarray(b_[:nb_pre]) for _, b_, _, _ in joins])
    _, kv.pool = prefill_into(backend.params, jnp.asarray(pre), kv.pool,
                              blks, jnp.asarray(slot_ids))
    kv.active[slot_ids] = True
    kv.grow_for_window(np.full(kv.max_slots, cont, np.int32))
    tables = jnp.asarray(kv.tables[slot_ids])

    sfx = rng.integers(0, cfg.vocab_size, (rows, suffix_len), dtype=np.int32)
    args = (jnp.asarray(sfx), jnp.full((rows,), prefix_len, jnp.int32),
            jnp.full((rows,), suffix_len, jnp.int32), tables)
    step_fn = backend.prefill_window_fn(kv.bs, suffix_len)
    chunk_fn = backend.prefill_window_fn(kv.bs, suffix_len, chunk=chunk)

    f_step, pool_step = step_fn(backend.params, kv.pool, *args)
    f_chunk, pool_chunk = chunk_fn(backend.params, kv.pool, *args)

    # decode continuation on both result pools: reads back the suffix KV
    decode_window = backend.paged_fns(kv.bs, window=cont)[2]
    pos_after = jnp.full((rows,), total, jnp.int32)
    steps = jnp.full((rows,), cont, jnp.int32)
    out_s, _ = decode_window(backend.params, pool_step, f_step[:, None],
                             pos_after, steps, tables)
    out_c, _ = decode_window(backend.params, pool_chunk, f_chunk[:, None],
                             pos_after, steps, tables)
    tokens_match = bool((np.asarray(f_step) == np.asarray(f_chunk)).all()
                        and (np.asarray(out_s) == np.asarray(out_c)).all())

    us_step = _time_call(lambda: step_fn(backend.params, kv.pool, *args),
                         reps)
    us_chunk = _time_call(lambda: chunk_fn(backend.params, kv.pool, *args),
                          reps)
    tokens_total = rows * suffix_len
    n_chunks = -(-suffix_len // chunk)
    row = {
        "rows": rows,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "chunk": chunk,
        "tokens_total": tokens_total,
        "dispatches_per_token": n_chunks / tokens_total,
        "dispatches_per_token_stepwise": suffix_len / tokens_total,
        "tokens_per_s": tokens_total * 1e6 / us_chunk,
        "tokens_per_s_stepwise": tokens_total * 1e6 / us_step,
        "tokens_match": tokens_match,
    }
    print(f"  prefill C={chunk} sfx={suffix_len} rows={rows}: "
          f"{n_chunks} vs {suffix_len} seq steps "
          f"({suffix_len / n_chunks:.1f}x), "
          f"{row['tokens_per_s']:.0f} vs {row['tokens_per_s_stepwise']:.0f} "
          f"tok/s, match={tokens_match}")
    return row


# --------------------------------------------------------------------------- #
# 4. speculative decode: draft_loop + verify_window vs stepwise greedy
# --------------------------------------------------------------------------- #
# Modeled cross-tier venue seconds (ADR-008; matches the serving sweep's
# TIER_STEP_S): the draft runs its k proposal steps (+ catch-up) on the
# cheap tier at ``draft_cost`` of a full step, then ONE chunked verify
# pass runs on the large tier.  Wall time on this CPU container measures
# interpret-mode dispatch overhead, so the modeled ratio is the
# hardware-independent claim — exactly like ``dispatches_per_token``.
SPEC_DRAFT_STEP_S = 0.32      # basic-tier step (TIER_STEP_S["basic"])
SPEC_VERIFY_STEP_S = 0.08     # large-tier step (TIER_STEP_S["large"])


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def spec_bench(arch: str, *, slots: int, k_max: int, budget: int,
               flip_p: float, prompt_len: int, draft_cost: float,
               seed: int = 0):
    """One acceptance point of the speculative sweep (oracle draft whose
    proposals are corrupted with probability ``flip_p``)."""
    from repro.models import model

    cfg = reduced_config(get_config(arch))
    backend = LMBackend(cfg, capacity=64, draft="oracle")
    budgets = np.full((slots,), budget, np.int32)

    # --- stepwise greedy reference (1 target dispatch / token) ----------
    kv, tok = _paged_setup(backend, slots, prompt_len, budgets)
    decode_slots = backend.paged_fns(kv.bs)[1]
    ref_out = np.zeros((slots, budget), np.int32)
    cur = tok.copy()
    jax.block_until_ready(decode_slots(
        backend.params, jax.tree.map(jnp.copy, kv.pool),
        jnp.asarray(cur[:, None]), jnp.asarray(kv.pos),
        jnp.asarray(kv.tables)))                      # warm compile
    t0 = time.perf_counter()
    for t in range(budget):
        kv.grow_for_write()
        nxt, kv.pool = decode_slots(
            backend.params, kv.pool, jnp.asarray(cur[:, None]),
            jnp.asarray(np.minimum(kv.pos, backend.capacity - 1)),
            jnp.asarray(kv.tables))
        cur = np.asarray(nxt, np.int32)
        ref_out[:, t] = cur
        kv.pos[:] = np.minimum(kv.pos + 1, kv.capacity)
    stepwise_s = time.perf_counter() - t0

    # --- speculative rounds (draft on cheap tier, verify on large) ------
    kv2, tok2 = _paged_setup(backend, slots, prompt_len, budgets)
    dpool = backend.init_draft_pool(kv2.max_slots, kv2.num_blocks, kv2.bs)
    # same seed as _paged_setup: the committed history the draft replays
    # (position-indexed, so the pending first token rides at index p)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (slots, prompt_len), dtype=np.int32)
    hist = [prompts[i].tolist() + [int(tok2[i])] for i in range(slots)]
    rng = np.random.default_rng(seed + 1)
    verify_fn = backend.spec_verify_fn(kv2.bs)
    cur, p = tok2.copy(), kv2.pos.copy()
    dp = np.zeros((slots,), np.int32)
    left = budgets.copy()
    out = [[] for _ in range(slots)]
    rounds = draft_steps = proposed = accepted = 0
    t0 = time.perf_counter()
    while (left > 0).any():
        live = left > 0
        kv2.active[:] = live
        room = np.maximum(kv2.capacity - 1
                          - np.minimum(p, kv2.capacity - 1), 0)
        k = np.where(live,
                     np.maximum(np.minimum(np.minimum(k_max, left - 1),
                                           room), 0), 0).astype(np.int32)
        kv2.grow_for_window(np.where(live, k + 1, 0).astype(np.int32))
        tables = jnp.asarray(kv2.tables)
        if int(k.sum()):
            n_c = np.where(live, p - dp, 0).astype(np.int32)
            tcpad = _pow2(max(int(n_c.max()), 1))
            ctoks = np.zeros((slots, tcpad), np.int32)
            for i in range(slots):
                if n_c[i]:
                    ctoks[i, :n_c[i]] = hist[i][dp[i]:p[i]]
            draft_fn = backend.spec_draft_fn(kv2.bs, tcpad, k_max)
            drafts, dpool = draft_fn(
                backend.draft_params, dpool, jnp.asarray(ctoks),
                jnp.asarray(np.where(live, dp, 0).astype(np.int32)),
                jnp.asarray(n_c), jnp.asarray(cur[:, None]),
                jnp.asarray(np.where(live, np.minimum(p, kv2.capacity - 1),
                                     0).astype(np.int32)),
                jnp.asarray(k), tables)
            drafts = np.asarray(drafts, np.int32)
            flips = rng.random((slots, k_max)) < flip_p
            drafts = np.where(flips, (drafts + 1) % cfg.vocab_size, drafts)
            draft_steps += tcpad + int(k.max())
            dp = np.where(live, p + k, dp)
        else:
            # every row clamped to k=0 (budget tails): no draft dispatch,
            # the verify degenerates to one plain greedy token per row —
            # same degrade the serving layer uses (ADR-008)
            drafts = np.zeros((slots, k_max), np.int32)
        x = np.concatenate([cur[:, None], drafts], axis=1)
        n_live = np.where(live, k + 1, 0).astype(np.int32)
        greedy, kv2.pool = verify_fn(
            backend.params, kv2.pool, jnp.asarray(x),
            jnp.asarray(np.where(live, np.minimum(p, kv2.capacity - 1),
                                 0).astype(np.int32)),
            jnp.asarray(n_live), tables)
        greedy = np.asarray(greedy, np.int32)
        acc = model.spec_accept(greedy, drafts, np.where(live, k, 0))
        for i in range(slots):
            if live[i]:
                got = greedy[i, :acc[i] + 1].tolist()
                out[i].extend(got)
                hist[i].extend(got)
        emitted = np.where(live, acc + 1, 0).astype(np.int32)
        cur = np.where(live, greedy[np.arange(slots), acc], cur)
        p = np.where(live, np.minimum(p + emitted, kv2.capacity), p)
        kv2.pos[:] = p                   # keep block reservation in step
        left = left - emitted
        dp = np.where(live, np.minimum(dp, p), dp)
        rounds += 1
        proposed += int(np.where(live, k, 0).sum())
        accepted += int(acc.sum())
    spec_s = time.perf_counter() - t0

    tokens_total = int(budgets.sum())
    tokens_match = all(out[i] == ref_out[i, :budgets[i]].tolist()
                       for i in range(slots))
    modeled_spec_s = (draft_steps * SPEC_DRAFT_STEP_S * draft_cost
                      + rounds * SPEC_VERIFY_STEP_S)
    modeled_plain_s = budget * SPEC_VERIFY_STEP_S
    row = {
        "slots": slots,
        "k_max": k_max,
        "budget": budget,
        "flip_p": flip_p,
        "draft_cost": draft_cost,
        "tokens_emitted": tokens_total,
        "rounds": rounds,
        "acceptance_rate": accepted / max(proposed, 1),
        "tokens_per_round": tokens_total / max(rounds * slots, 1) * slots,
        "dispatches_per_token": rounds / budget,
        "dispatches_per_token_stepwise": 1.0,
        "spec_speedup": modeled_plain_s / modeled_spec_s,
        "us_per_token": spec_s * 1e6 / tokens_total,
        "us_per_token_stepwise": stepwise_s * 1e6 / tokens_total,
        "tokens_match": tokens_match,
    }
    print(f"  spec k={k_max} flip={flip_p:.1f}: "
          f"accept={row['acceptance_rate']:.2f} "
          f"{row['dispatches_per_token']:.2f} target dispatches/token, "
          f"modeled speedup {row['spec_speedup']:.2f}x, "
          f"match={tokens_match}")
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI")
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel sweep in the Pallas interpreter "
                         "(the CPU); without it the kernels compile for "
                         "the TPU")
    ap.add_argument("--reps", type=int, default=0,
                    help="timing repetitions (0 = auto)")
    ap.add_argument("--draft-cost", type=float, default=0.1,
                    help="modeled draft step cost as a fraction of a full "
                         "step (the smoke model's parameter ratio is "
                         "embedding-dominated, so this is explicit)")
    ap.add_argument("--json", default="BENCH_decode.json",
                    help="output artifact path ('' to disable)")
    args = ap.parse_args()
    use_compile_cache()

    interpret = args.interpret
    reps = args.reps or (3 if args.smoke else 15)
    if args.smoke:
        cases = [(2, 1, 8), (4, 2, 8)]
        b, ctx_blocks, d = 2, 2, 16
        loop_cfgs = [(2, 4)]
        pf_cfgs = [(2, 8, 16, 8)]              # (rows, prefix, suffix, chunk)
        spec_cfgs = [(2, 4, 16, 0.0), (2, 4, 16, 0.5)]
    else:
        cases = [(2, 2, 8), (4, 2, 8), (4, 1, 8), (8, 2, 8),
                 (8, 2, 16), (4, 1, 16)]
        b, ctx_blocks, d = 4, 4, 32
        loop_cfgs = [(4, 4), (4, 8)]
        pf_cfgs = [(2, 8, 16, 8), (4, 8, 24, 8), (4, 16, 16, 4)]
        # (slots, k_max, budget, flip_p): acceptance sweep from oracle
        # agreement down to near-total draft/target disagreement
        spec_cfgs = [(4, 4, 16, 0.0), (4, 4, 16, 0.4), (4, 4, 16, 0.9),
                     (4, 2, 16, 0.0)]

    print("kernel sweep (fused vs per-head paged attention):")
    sweep = kernel_sweep(cases, b=b, ctx_blocks=ctx_blocks, d=d, reps=reps,
                         interpret=interpret)
    print("decode loop (window scan vs per-token dispatch):")
    loops = []
    for slots, window in loop_cfgs:
        loops.append(decode_loop_bench(args.arch, slots=slots, window=window,
                                       prompt_len=6, reps=reps,
                                       donate=False))
    # donation A/B on the largest window
    slots, window = loop_cfgs[-1]
    loops.append(decode_loop_bench(args.arch, slots=slots, window=window,
                                   prompt_len=6, reps=reps, donate=True))
    print("prefill loop (chunked vs stepwise suffix prefill):")
    prefills = []
    for rows, prefix_len, suffix_len, chunk in pf_cfgs:
        prefills.append(prefill_bench(args.arch, rows=rows,
                                      prefix_len=prefix_len,
                                      suffix_len=suffix_len, chunk=chunk,
                                      reps=reps))
    print("speculative decode (draft + chunked verify vs stepwise):")
    specs = []
    for slots, k_max, budget, flip_p in spec_cfgs:
        specs.append(spec_bench(args.arch, slots=slots, k_max=k_max,
                                budget=budget, flip_p=flip_p, prompt_len=6,
                                draft_cost=args.draft_cost))

    doc = {
        "benchmark": "decode_micro",
        "arch": args.arch,
        "interpret": interpret,
        "smoke": args.smoke,
        "kernel_sweep": sweep,
        "decode_loop": loops,
        "prefill_loop": prefills,
        "spec": specs,
    }
    if args.json:
        path = os.path.join(os.path.dirname(__file__), "..", args.json)
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {os.path.normpath(path)}")
    ok = all(r["tokens_match"] for r in loops + prefills + specs)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
