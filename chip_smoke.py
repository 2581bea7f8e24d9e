#!/usr/bin/env python3
"""Bring-up check: serve smollm-360m at its published widths on one TPU.

    python chip_smoke.py [--seed N]

Run from the repository root; it puts ``src`` on the import path itself.
One process holds the chip from start to end, and the script exits
non-zero unless JAX's first device is a TPU.  With random bf16 weights
from the seed (32 layers, 960 wide, 15/5 heads, vocab 49152) it

1. runs one prefill, one paged decode step and one paged prefill chunk
   through the served Pallas path and compares their logits with a plain
   float32 reference (``repro.models.reference``) at the same positions;
2. serves 8 seeded requests through ``ClientHandler``'s default path
   (paged KV, prefix cache, chunked prefill, mixed dispatch) with an
   executor that runs each dispatch once on the wall clock;
3. checks every request: its token count, its ids, and that each served
   token's reference logit is within a margin of the reference maximum;
4. checks that the compiled paged decode step holds Mosaic kernels
   (``tpu_custom_call``) and is not interpreted.

Times printed on the way are wall seconds on the host, compiles included.
The last line of the output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "smollm-360m"
N_REQUESTS = 8
PROMPT_LENS = (32, 128)      # seeded prompt lengths, inclusive
SHARED_PREFIX = 64           # half the requests open with one system prompt
PROMPT_PAD = 128             # the handler pads every prompt to this length
NEW_TOKENS = 16
MAX_BATCH = 4
BLOCK_SIZE = 8
CAPACITY = 256               # KV positions per slot: 32 blocks of 8
CHUNK = 8                    # tokens in the checked paged prefill chunk
REF_LEN = 160                # every reference sequence is padded to this

# Agreement with the float32 reference.  The served path keeps weights and
# the activations between ops in bf16 (unit roundoff 2^-9) and accumulates
# every matmul and kernel in float32.  Errors are in units of the standard
# deviation of the reference logits over the vocabulary (about 1 here).
#
# REL_RMS_TOL bounds the RMS logit error per position.  On the CPU this
# model's bf16 path measured 0.0096, 0.0131 and 0.0145 at 1, 4 and 8
# layers; 32 layers should land near 0.02.  A matmul accumulated in bf16
# instead loses about 2^-9 * sqrt(K/2): 0.04 at K=960 and 0.07 at
# K=2560 per matmul, which over 32 layers lands well above the bound; bf16
# weights rounded once more to fp8 measured 0.14.
REL_RMS_TOL = 0.04
# MAX_ABS_TOL bounds the worst logit of a position (CPU: 0.036-0.049 at
# 2 layers), with the same growth allowance.
MAX_ABS_TOL = 0.25
# A served token was the argmax of served logits that sit within
# MAX_ABS_TOL of the reference, so its reference logit is within twice
# that of the reference maximum.  A token from a wrong position or a
# wrong cache block is a random draw, about 4 deviations below the top.
TOKEN_MARGIN = 2 * MAX_ABS_TOL


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel_errors(served, ref):
    """Per-row (rms error, max abs error), both over std of ref."""
    import numpy as np
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    std = ref.std(-1)
    rms = np.sqrt(np.mean((served - ref) ** 2, -1)) / std
    mx = np.max(np.abs(served - ref), -1) / std
    return rms, mx


def make_requests(vocab: int, seed: int):
    """Seeded trace: prompts of 32-128 tokens, half of them sharing a
    64-token system prompt, open-loop exponential arrivals."""
    import numpy as np
    from repro.core.scheduler import ServeRequest
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, SHARED_PREFIX, dtype=np.int32)
    reqs, t = [], 0.0
    for i in range(N_REQUESTS):
        n = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
        prompt = rng.integers(0, vocab, n, dtype=np.int32)
        if i % 2 == 1:
            n = max(n, SHARED_PREFIX + 8)
            prompt = np.concatenate(
                [system, rng.integers(0, vocab, n - SHARED_PREFIX,
                                      dtype=np.int32)])
        t += float(rng.exponential(0.05))
        reqs.append(ServeRequest(i, prompt, NEW_TOKENS, arrival_t=t))
    return reqs


class CompileMeter:
    """Counts backend compiles and their seconds in this process."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


def check_reference_agreement(backend, ref_fn, seed: int, *,
                              require_kernels: bool) -> dict:
    """Prefill, one paged decode step and one paged prefill chunk of
    MAX_BATCH rows through the served path, against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model
    cfg, ctx, params = backend.cfg, backend.ctx, backend.params
    b, p, bs = MAX_BATCH, PROMPT_PAD, BLOCK_SIZE
    rng = np.random.default_rng(seed + 1)
    prompts = rng.integers(0, cfg.vocab_size, (b, p), dtype=np.int32)
    chunk = rng.integers(0, cfg.vocab_size, (b, CHUNK), dtype=np.int32)
    max_blk = CAPACITY // bs
    tables = (1 + np.arange(b * max_blk, dtype=np.int32)).reshape(b, max_blk)
    pool = backend.init_paged_pool(b, b * max_blk + 1, bs)
    prefill_into = backend.paged_fns(bs)[0]
    firsts, pool = prefill_into(params, jnp.asarray(prompts), pool,
                                jnp.asarray(tables[:, :p // bs]),
                                jnp.arange(b, dtype=jnp.int32))
    prefill_logits = jax.jit(lambda prm, t: model.forward(
        cfg, prm, {"tokens": t}, ctx, "prefill")[0])
    lg_prefill = prefill_logits(params, jnp.asarray(prompts))

    def decode(prm, pool, tok, pos, tables):
        return model.decode_step(cfg, prm, pool, tok, pos, ctx,
                                 block_tables=tables, block_size=bs)

    def chunk_step(prm, pool, toks, pos0, n_live, tables):
        return model.chunk_step(cfg, prm, pool, toks, pos0, n_live, ctx,
                                block_tables=tables, block_size=bs,
                                capacity=CAPACITY)

    tok = jnp.asarray(firsts, jnp.int32)[:, None]
    pos = jnp.full((b,), p, jnp.int32)
    dev_tables = jnp.asarray(tables)
    dec = jax.jit(decode).lower(params, pool, tok, pos, dev_tables).compile()
    lg_decode, pool = dec(params, pool, tok, pos, dev_tables)
    pos0 = jnp.full((b,), p + 1, jnp.int32)
    n_live = jnp.full((b,), CHUNK, jnp.int32)
    chk = jax.jit(chunk_step).lower(params, pool, jnp.asarray(chunk), pos0,
                                    n_live, dev_tables).compile()
    lg_chunk, pool = chk(params, pool, jnp.asarray(chunk), pos0, n_live,
                         dev_tables)
    if require_kernels:
        for name, c in (("decode", dec), ("chunk", chk)):
            if "tpu_custom_call" not in c.as_text():
                raise AssertionError(f"compiled paged {name} step holds no "
                                     "Mosaic kernel (tpu_custom_call)")

    seqs = np.concatenate([prompts, np.asarray(firsts)[:, None], chunk], 1)
    ref = np.asarray(ref_fn(seqs))
    out = {}
    for name, served, at in (("prefill", lg_prefill, p - 1),
                             ("decode", lg_decode, p),
                             ("chunk", lg_chunk, p + CHUNK)):
        rms, mx = _rel_errors(served, ref[:, at])
        out[name] = (float(rms.max()), float(mx.max()))
        log(f"reference agreement  {name:7s} at position {at}: rel-rms "
            f"{rms.max():.5f} (tol {REL_RMS_TOL}), max-abs/std "
            f"{mx.max():.5f} (tol {MAX_ABS_TOL})")
        if rms.max() > REL_RMS_TOL or mx.max() > MAX_ABS_TOL:
            raise AssertionError(f"{name} logits disagree with the float32 "
                                 "reference beyond tolerance")
    return out


def serve_requests(backend, reqs):
    """Serve the trace through ClientHandler's default paged path."""
    from repro.launch.serve import ClientHandler, measured_executor
    dispatches = []

    def executor(clone, fn, args):
        value, secs = measured_executor(clone, fn, args)
        dispatches.append(secs)
        return value, secs

    handler = ClientHandler(backend, max_batch=MAX_BATCH,
                            prompt_pad=PROMPT_PAD, block_size=BLOCK_SIZE,
                            max_secondaries=0, executor=executor)
    t0 = time.perf_counter()
    report = handler.run(reqs)
    wall = time.perf_counter() - t0
    return handler, report, wall, dispatches


def check_served_tokens(backend, handler, report, reqs, ref_fn) -> float:
    """Every request returns NEW_TOKENS ids in range, each within
    TOKEN_MARGIN of the reference's best logit at its position."""
    import numpy as np
    from repro.launch.serve import _SlotEngine
    vocab = backend.cfg.vocab_size
    done = {c.rid: c for c in report.completions}
    if sorted(done) != [r.rid for r in reqs]:
        raise AssertionError(f"served {sorted(done)} of {len(reqs)} requests")
    seqs = np.zeros((len(reqs), REF_LEN), np.int32)
    for i, r in enumerate(reqs):
        toks = np.asarray(done[r.rid].tokens, np.int64)
        if len(toks) != r.max_new_tokens:
            raise AssertionError(f"request {r.rid} returned {len(toks)} "
                                 f"tokens, wants {r.max_new_tokens}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {r.rid} returned ids outside "
                                 f"[0, {vocab})")
        eff = _SlotEngine.effective_prompt(r, PROMPT_PAD, backend.capacity)
        seq = np.concatenate([eff, toks[:-1]])
        seqs[i, :len(seq)] = seq
    ref = np.asarray(ref_fn(seqs))
    worst = 0.0
    for i, r in enumerate(reqs):
        toks = np.asarray(done[r.rid].tokens)
        rows = ref[i, PROMPT_PAD - 1:PROMPT_PAD - 1 + len(toks)]
        gap = (rows.max(-1) - rows[np.arange(len(toks)), toks]) \
            / rows.std(-1)
        worst = max(worst, float(gap.max()))
    log(f"served tokens: {len(reqs)} requests x {NEW_TOKENS} tokens, worst "
        f"reference gap {worst:.5f} std (margin {TOKEN_MARGIN})")
    if worst > TOKEN_MARGIN:
        raise AssertionError("a served token's reference logit is below "
                             "the reference maximum by more than the margin")
    return worst


def check_served_step_is_mosaic(backend, handler) -> None:
    """The served paged decode step, compiled at the served shapes."""
    import jax.numpy as jnp
    kv = next(iter(handler._kv_pools.values()))
    decode_window = backend.paged_fns(BLOCK_SIZE)[2]
    s = kv.max_slots
    zeros = jnp.zeros((s,), jnp.int32)
    text = decode_window.lower(
        backend.params, kv.pool, jnp.zeros((s, 1), jnp.int32), zeros, zeros,
        jnp.zeros((s, kv.max_blk), jnp.int32)).compile().as_text()
    n = text.count("tpu_custom_call")
    log(f"served decode step: {n} tpu_custom_call sites in the compiled HLO")
    if n == 0:
        raise AssertionError("the served paged decode step holds no Mosaic "
                             "kernel")


def run(cfg, *, seed: int, require_kernels: bool = True) -> None:
    """All phases on ``cfg``; raises on the first failed check."""
    import jax
    import numpy as np
    from repro.launch.serve import LMBackend
    from repro.models import reference
    meter = CompileMeter()
    t0 = time.perf_counter()
    backend = LMBackend(cfg, capacity=CAPACITY, seed=seed)
    log(f"model: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}; attention impl {backend.ctx.impl}")

    ref_batch = jax.jit(lambda prm, rows: jax.vmap(
        lambda t: reference.forward_logits(cfg, prm, t))(rows))

    def ref_fn(seqs):
        # one shape for every call: causal attention keeps the padding
        # past a sequence's end from reaching its positions
        rows = np.zeros((N_REQUESTS, REF_LEN), np.int32)
        rows[:len(seqs), :seqs.shape[1]] = seqs
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref_batch(backend.params, rows))[:len(seqs)]

    check_reference_agreement(backend, ref_fn, seed,
                              require_kernels=require_kernels)
    log(f"phase reference: {time.perf_counter() - t0:.3f} s wall, "
        f"{meter.n} compiles ({meter.seconds:.3f} s)")

    reqs = make_requests(cfg.vocab_size, seed)
    handler, report, wall, dispatches = serve_requests(backend, reqs)
    log(f"phase serve: {len(reqs)} requests, {len(dispatches)} dispatches, "
        f"{wall:.3f} s wall ({wall / len(reqs):.3f} s per request), "
        f"dispatch median {np.median(dispatches):.4f} s, slowest "
        f"{max(dispatches):.3f} s (compiles included), prefix hit rate "
        f"{report.prefix_hit_rate:.3f}; {meter.n} compiles so far "
        f"({meter.seconds:.3f} s)")
    check_served_tokens(backend, handler, report, reqs, ref_fn)
    if require_kernels:
        check_served_step_is_mosaic(backend, handler)
    log(f"total: {time.perf_counter() - t0:.3f} s wall, {meter.n} compiles "
        f"({meter.seconds:.3f} s)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and arrivals")
    args = ap.parse_args()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {use_compile_cache()}")
    run(get_config(ARCH), seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
