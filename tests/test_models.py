"""Per-arch smoke tests (reduced configs) + prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import draft_config, get_config, list_archs, \
    reduced_config
from repro.models import model
from repro.models.context import RunContext

KEY = jax.random.PRNGKey(3)


def make_batch(cfg, b, s, key=KEY):
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.frontend == "audio":
        return {"frames": jax.random.normal(k1, (b, s, cfg.d_model)),
                "targets": jax.random.randint(k2, (b, s), 0, cfg.vocab_size),
                "loss_mask": jnp.ones((b, s), jnp.float32)}
    if cfg.frontend == "vision":
        p = cfg.n_patches
        return {"patches": jax.random.normal(k1, (b, p, cfg.d_model)),
                "tokens": jax.random.randint(k2, (b, s - p), 0,
                                             cfg.vocab_size),
                "targets": jax.random.randint(k3, (b, s - p), 0,
                                              cfg.vocab_size)}
    return {"tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (b, s), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", list_archs())
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced config: one forward + one train step, shapes + finite."""
    from repro.launch import steps as S
    from repro.optim.adamw import OptConfig

    cfg = reduced_config(get_config(arch))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    b, s = 2, 16
    batch = make_batch(cfg, b, s)
    loss, metrics = model.forward(cfg, params, batch, ctx, "train")
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"

    step = jax.jit(S.build_train_step(cfg, OptConfig(), ctx))
    state = {"params": params, "opt": __import__(
        "repro.optim.adamw", fromlist=["init"]).init(params)}
    new_state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(new_state["opt"]["step"]) == 1
    # params actually changed
    diff = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b_.astype(jnp.float32)))),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(diff)) > 0


@pytest.mark.parametrize("arch", [a for a in list_archs()
                                  if not get_config(a).encoder_only])
def test_prefill_decode_consistency(arch):
    """Incremental decode must match fresh prefill logits (serving oracle)."""
    cfg = reduced_config(get_config(arch))
    ctx = RunContext(moe_capacity_factor=(cfg.n_experts / cfg.top_k
                                          if cfg.is_moe else 1.25))
    params = model.init(cfg, KEY)
    b, s, s0 = 2, 12, 4
    if cfg.frontend == "vision":
        pytest.skip("vlm decode covered via text path (prefix in cache)")
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)

    ref_logits = []
    for t in range(s0, s + 1):
        lg, _ = model.forward(cfg, params, {"tokens": toks[:, :t]}, ctx,
                              "prefill")
        ref_logits.append(np.asarray(lg, np.float32))

    lg, cache = model.forward(cfg, params, {"tokens": toks[:, :s0]}, ctx,
                              "prefill", cache_capacity=s)
    outs = [np.asarray(lg, np.float32)]
    for t in range(s0, s):
        lg, cache = model.decode_step(cfg, params, cache, toks[:, t:t + 1],
                                      jnp.int32(t), ctx)
        outs.append(np.asarray(lg, np.float32))
    for i, (a, b_) in enumerate(zip(ref_logits, outs)):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-3,
                                   err_msg=f"{arch} step {i}")


def test_sliding_window_ring_decode_past_window():
    """Decode beyond the window: ring cache must equal fresh prefill."""
    import dataclasses
    cfg = reduced_config(get_config("mixtral-8x7b"))
    cfg = dataclasses.replace(cfg, window=4)
    ctx = RunContext(moe_capacity_factor=cfg.n_experts / cfg.top_k)
    params = model.init(cfg, KEY)
    b, s = 1, 12
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    # prefill exactly the window, decode 8 more (wraps the ring twice)
    lg, cache = model.forward(cfg, params, {"tokens": toks[:, :4]}, ctx,
                              "prefill")
    for t in range(4, s):
        want, _ = model.forward(cfg, params, {"tokens": toks[:, :t]}, ctx,
                                "prefill")
        np.testing.assert_allclose(np.asarray(lg), np.asarray(want),
                                   atol=5e-4, rtol=5e-3, err_msg=f"t={t}")
        lg, cache = model.decode_step(cfg, params, cache, toks[:, t:t + 1],
                                      jnp.int32(t), ctx)


def test_paligemma_prefix_attention_bidirectional():
    """Patch positions must see later patches (prefix-LM), text stays causal."""
    cfg = reduced_config(get_config("paligemma-3b"))
    params = model.init(cfg, KEY)
    ctx = RunContext()
    b, s = 1, 8
    batch = make_batch(cfg, b, s)
    # perturb the LAST patch; prefix-LM => loss must change (first patch
    # attends to it), while under causal-only it could not affect position 0
    lg1, _ = model.forward(cfg, params, batch, ctx, "prefill")
    batch2 = dict(batch)
    batch2["patches"] = batch["patches"].at[:, -1].add(10.0)
    lg2, _ = model.forward(cfg, params, batch2, ctx, "prefill")
    assert float(jnp.max(jnp.abs(lg1 - lg2))) > 0


def test_pallas_impl_matches_xla_impl():
    """Reduced model forward with impl=pallas (interpret) == impl=xla."""
    for arch in ("qwen2.5-3b", "rwkv6-7b", "recurrentgemma-2b"):
        cfg = reduced_config(get_config(arch))
        params = model.init(cfg, KEY)
        batch = make_batch(cfg, 2, 16)
        l1, _ = model.forward(cfg, params, batch,
                              RunContext(impl="xla"), "train")
        l2, _ = model.forward(cfg, params, batch,
                              RunContext(impl="interpret"), "train")
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3,
                                   err_msg=arch)


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen2.5-3b",
                                  "phi3-mini-3.8b"])
def test_plain_reference_matches_model_forward(arch):
    """The plain float32 reference (no kernels, cache or shared layer
    code) gives the model's prefill logits at every prefix length."""
    from repro.models import reference
    cfg = reduced_config(get_config(arch))
    assert reference.supports(cfg)
    params = model.init(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 12), 0,
                              cfg.vocab_size)
    ref = reference.forward_logits(cfg, params, toks[0])
    for n in (1, 7, 12):
        got, _ = model.forward(cfg, params, {"tokens": toks[:, :n]},
                               RunContext(), "prefill")
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[n - 1]),
                                   atol=2e-5, rtol=2e-5)


def test_moe_capacity_drops_are_bounded():
    """With capacity factor E/k, no tokens are dropped (exact routing)."""
    cfg = reduced_config(get_config("olmoe-1b-7b"))
    params = model.init(cfg, KEY)
    batch = make_batch(cfg, 2, 16)
    full = RunContext(moe_capacity_factor=cfg.n_experts / cfg.top_k)
    tight = RunContext(moe_capacity_factor=0.25)
    l_full, _ = model.forward(cfg, params, batch, full, "train")
    l_tight, _ = model.forward(cfg, params, batch, tight, "train")
    assert np.isfinite(float(l_full)) and np.isfinite(float(l_tight))


def _paged_decode_state(cfg, ctx, params, prompt_lens, block_size, capacity):
    """Write each slot's random prompt into a paged pool via decode steps.

    Returns (cache, tables, tok (B,1), pos (B,)) — the state a serving
    engine would hold right before a decode window.
    """
    slots = len(prompt_lens)
    max_blk = capacity // block_size
    pool = model.init_paged_cache(cfg, slots, slots * max_blk + 1,
                                  block_size)
    rng = np.random.default_rng(11)
    tables = np.zeros((slots, max_blk), np.int32)
    nxt_blk = 1
    for i, ln in enumerate(prompt_lens):
        for j in range(-(-ln // block_size)):
            tables[i, j] = nxt_blk
            nxt_blk += 1
    prompts = [rng.integers(0, cfg.vocab_size, ln).astype(np.int32)
               for ln in prompt_lens]
    tok = np.zeros((slots, 1), np.int32)
    pos = np.zeros((slots,), np.int32)
    for t in range(max(prompt_lens)):
        cur = np.array([[p[t] if t < len(p) else 0] for p in prompts],
                       np.int32)
        live = np.array([t < len(p) for p in prompts])
        logits, pool = model.decode_step(
            cfg, params, pool, jnp.asarray(cur),
            jnp.asarray(np.where(live, pos, 0)), ctx,
            block_tables=jnp.asarray(np.where(live[:, None], tables, 0)),
            block_size=block_size)
        nx = np.asarray(jnp.argmax(logits, -1), np.int32)
        tok = np.where(live[:, None], nx[:, None], tok)
        pos = np.where(live, pos + 1, pos)
    return pool, tables, tok, pos


def _stepwise_decode(cfg, ctx, params, cache, tables, tok, pos, budgets,
                     block_size, capacity, num_steps):
    """The PR-2 per-token path: T decode_step dispatches with host masking
    between steps (dead rows -> trash block), mirroring the serving loop."""
    cur, p = tok[:, 0].copy(), pos.copy()
    out = np.zeros((len(budgets), num_steps), np.int32)
    for t in range(num_steps):
        live = t < budgets
        logits, cache = model.decode_step(
            cfg, params, cache, jnp.asarray(cur[:, None]),
            jnp.asarray(np.where(live, np.minimum(p, capacity - 1), 0)),
            ctx,
            block_tables=jnp.asarray(np.where(live[:, None], tables, 0)),
            block_size=block_size)
        nx = np.asarray(jnp.argmax(logits, -1), np.int32)
        cur = np.where(live, nx, cur)
        out[:, t] = cur
        p = np.where(live, np.minimum(p + 1, capacity), p)
    return out, cache


def test_decode_loop_matches_stepwise_decode():
    """decode_loop(T) — one on-device scan — must emit token-identical
    output to T host-driven decode_step calls, across ragged budgets
    (mid-window completions park in the trash block) and an inactive slot,
    and leave a bitwise-identical KV pool behind."""
    cfg = reduced_config(get_config("smollm-360m"))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    bs, cap, T = 4, 16, 4
    cache, tables, tok, pos = _paged_decode_state(
        cfg, ctx, params, prompt_lens=[3, 5, 1], block_size=bs, capacity=cap)
    budgets = np.array([T, 2, 0], np.int32)     # full / mid-window / empty

    want, cache_ref = _stepwise_decode(
        cfg, ctx, params, jax.tree.map(jnp.copy, cache), tables, tok, pos,
        budgets, bs, cap, T)
    got, cache_win = model.decode_loop(
        cfg, params, jax.tree.map(jnp.copy, cache), jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(budgets), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, num_steps=T,
        capacity=cap)
    np.testing.assert_array_equal(np.asarray(got), want)
    for a, b in zip(jax.tree.leaves(cache_ref), jax.tree.leaves(cache_win)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_loop_past_capacity_clamps_like_stepwise():
    """A window running past ``capacity`` pins its writes to the last cell
    exactly like the per-token path (the contiguous-path clamp rule)."""
    cfg = reduced_config(get_config("smollm-360m"))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    bs, cap, T = 4, 8, 6
    cache, tables, tok, pos = _paged_decode_state(
        cfg, ctx, params, prompt_lens=[6, 4], block_size=bs, capacity=cap)
    budgets = np.array([T, T], np.int32)        # slot 0 crosses capacity
    want, _ = _stepwise_decode(
        cfg, ctx, params, jax.tree.map(jnp.copy, cache), tables, tok, pos,
        budgets, bs, cap, T)
    got, _ = model.decode_loop(
        cfg, params, jax.tree.map(jnp.copy, cache), jnp.asarray(tok),
        jnp.asarray(pos), jnp.asarray(budgets), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, num_steps=T,
        capacity=cap)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_prefill_loop_matches_stepwise_prompt_feed():
    """The suffix-prefill scan (one dispatch) must write bitwise the KV a
    host-driven per-token prompt feed writes, and return the same first
    generated token — including a per-row *offset* start (the prefix-hit
    path: only the uncached suffix is fed) and an inactive pad row."""
    cfg = reduced_config(get_config("smollm-360m"))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    bs, cap = 4, 16
    prompt_lens = [6, 9, 3]
    # reference: prompts written via per-token decode steps (host loop)
    cache_ref, tables, tok_ref, pos_ref = _paged_decode_state(
        cfg, ctx, params, prompt_lens, block_size=bs, capacity=cap)
    rng = np.random.default_rng(11)             # same stream -> same prompts
    prompts = [rng.integers(0, cfg.vocab_size, ln).astype(np.int32)
               for ln in prompt_lens]
    # scan path: same prompts, same tables, fresh pool, plus a pad row
    pool = model.init_paged_cache(cfg, 3, 3 * (cap // bs) + 1, bs)
    tmax = max(prompt_lens)
    toks = np.zeros((3, tmax), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    firsts, pool = model.prefill_loop(
        cfg, params, pool, jnp.asarray(toks),
        jnp.asarray(np.zeros(3, np.int32)),
        jnp.asarray(np.asarray(prompt_lens, np.int32)), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, num_steps=tmax,
        capacity=cap)
    np.testing.assert_array_equal(np.asarray(firsts), tok_ref[:, 0])
    for a, b in zip(jax.tree.leaves(cache_ref), jax.tree.leaves(pool)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # offset restart: re-feed only the last 4 tokens of row 1's prompt
    # into the same pool (positions 5..8) — KV must stay bitwise stable
    # and the first token must reproduce (the restore-path invariant)
    sfx = np.zeros((3, 4), np.int32)
    sfx[1] = prompts[1][-4:]
    n_tok = np.array([0, 4, 0], np.int32)
    pos0 = np.array([0, prompt_lens[1] - 4, 0], np.int32)
    f2, pool2 = model.prefill_loop(
        cfg, params, pool, jnp.asarray(sfx), jnp.asarray(pos0),
        jnp.asarray(n_tok), ctx, block_tables=jnp.asarray(tables),
        block_size=bs, num_steps=4, capacity=cap)
    assert int(np.asarray(f2)[1]) == int(tok_ref[1, 0])
    for a, b in zip(jax.tree.leaves(cache_ref), jax.tree.leaves(pool2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# A C-token chunk projects and attends its C tokens in one matmul, the
# stepwise scan one token at a time, so XLA reduces them in different
# orders: float32 K/V then agree to rounding (at most 1.3e-6 seen at
# d_model 64), not bitwise.  1e-5 is ten times that, and far below what a
# misplaced or missing write costs (O(1) activations).
CHUNK_KV_ATOL = 1e-5


def _assert_live_blocks_equal(pool_a, pool_b, atol=0.0):
    """Compare every pool block except trash block 0: bitwise by default,
    within ``atol`` where the two paths reduce in different orders.

    Dead rows park their writes in the trash block, whose final contents
    legitimately differ between execution orders — no reader ever gathers
    it for a live position, so it is excluded from identity claims.
    Cache leaves are (..., N, Hkv, bs, D) with leading layer-group dims,
    so the block axis is ndim-4.
    """
    for a, b in zip(jax.tree.leaves(pool_a), jax.tree.leaves(pool_b)):
        ax = a.ndim - 4
        am = np.moveaxis(np.asarray(a), ax, 0)
        bm = np.moveaxis(np.asarray(b), ax, 0)
        if atol:
            np.testing.assert_allclose(am[1:], bm[1:], rtol=0, atol=atol)
        else:
            np.testing.assert_array_equal(am[1:], bm[1:])


def _staged_suffix_state(cfg, ctx, params, prefix_lens, n_tok, bs, cap,
                         seed=11):
    """Stage per-row prefixes into a paged pool; return the suffix batch.

    Returns (pool, tables, sfx_toks (B, tmax), pos0, n_tok) — the state
    right before a suffix prefill (prefix-hit join / restore): positions
    0..prefix_lens[i]-1 hold KV, the suffix tokens are not yet written.
    """
    slots = len(prefix_lens)
    max_blk = cap // bs
    pool = model.init_paged_cache(cfg, slots, slots * max_blk + 1, bs)
    rng = np.random.default_rng(seed)
    tables = np.zeros((slots, max_blk), np.int32)
    nxt = 1
    for i in range(slots):
        for j in range(max_blk):
            tables[i, j] = nxt
            nxt += 1
    pre_max = max(max(prefix_lens), 1)
    pre = np.zeros((slots, pre_max), np.int32)
    for i, ln in enumerate(prefix_lens):
        pre[i, :ln] = rng.integers(0, cfg.vocab_size, ln)
    _, pool = model.prefill_loop(
        cfg, params, pool, jnp.asarray(pre),
        jnp.asarray(np.zeros(slots, np.int32)),
        jnp.asarray(np.asarray(prefix_lens, np.int32)), ctx,
        block_tables=jnp.asarray(tables), block_size=bs,
        num_steps=pre_max, capacity=cap)
    tmax = max(n_tok)
    sfx = np.zeros((slots, tmax), np.int32)
    for i, n in enumerate(n_tok):
        sfx[i, :n] = rng.integers(0, cfg.vocab_size, n)
    return pool, tables, sfx, np.asarray(prefix_lens, np.int32), \
        np.asarray(n_tok, np.int32)


@pytest.mark.parametrize("chunk", [1, 2, 4, 8])
def test_prefill_chunks_matches_prefill_loop(chunk):
    """The chunked suffix scan (⌈T/chunk⌉ steps) must return the same
    first tokens as the stepwise scan (T steps) and leave every live pool
    block equal up to reduction order (``CHUNK_KV_ATOL``) — across ragged
    suffixes, nonzero start cursors, and a dead (n_tok == 0) pad row."""
    cfg = reduced_config(get_config("smollm-360m"))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    bs, cap = 4, 32
    pool, tables, sfx, pos0, n_tok = _staged_suffix_state(
        cfg, ctx, params, prefix_lens=[0, 8, 5], n_tok=[7, 9, 0],
        bs=bs, cap=cap)
    tmax = sfx.shape[1]
    f_ref, pool_ref = model.prefill_loop(
        cfg, params, jax.tree.map(jnp.copy, pool), jnp.asarray(sfx),
        jnp.asarray(pos0), jnp.asarray(n_tok), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, num_steps=tmax,
        capacity=cap)
    f_chk, pool_chk = model.prefill_chunks(
        cfg, params, jax.tree.map(jnp.copy, pool), jnp.asarray(sfx),
        jnp.asarray(pos0), jnp.asarray(n_tok), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, chunk=chunk,
        num_steps=-(-tmax // chunk), capacity=cap)
    live = n_tok > 0
    np.testing.assert_array_equal(np.asarray(f_chk)[live],
                                  np.asarray(f_ref)[live])
    _assert_live_blocks_equal(pool_ref, pool_chk, atol=CHUNK_KV_ATOL)


_CHUNK_FIX = []


def _chunk_fixture():
    """Shared tiny model for the chunked-prefill identity checks (built
    once so seeded sweeps and the hypothesis property re-use params)."""
    if not _CHUNK_FIX:
        cfg = reduced_config(get_config("smollm-360m"))
        _CHUNK_FIX.append((cfg, RunContext(), model.init(cfg, KEY)))
    return _CHUNK_FIX[0]


def _check_chunked_vs_stepwise(prefix_lens, n_tok, chunk, seed=11):
    """Stage seeded prefixes, then assert prefill_chunks == prefill_loop
    (first tokens on live rows exact, every live pool block within
    ``CHUNK_KV_ATOL``)."""
    cfg, ctx, params = _chunk_fixture()
    bs, cap = 4, 32
    n_tok = list(n_tok)
    if max(n_tok) == 0:
        n_tok[0] = 1                            # at least one live row
    pool, tables, sfx, pos0, nt = _staged_suffix_state(
        cfg, ctx, params, prefix_lens=list(prefix_lens), n_tok=n_tok,
        bs=bs, cap=cap, seed=seed)
    tmax = sfx.shape[1]
    f_ref, pool_ref = model.prefill_loop(
        cfg, params, jax.tree.map(jnp.copy, pool), jnp.asarray(sfx),
        jnp.asarray(pos0), jnp.asarray(nt), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, num_steps=tmax,
        capacity=cap)
    f_chk, pool_chk = model.prefill_chunks(
        cfg, params, jax.tree.map(jnp.copy, pool), jnp.asarray(sfx),
        jnp.asarray(pos0), jnp.asarray(nt), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, chunk=chunk,
        num_steps=-(-tmax // chunk), capacity=cap)
    live = nt > 0
    np.testing.assert_array_equal(np.asarray(f_chk)[live],
                                  np.asarray(f_ref)[live])
    _assert_live_blocks_equal(pool_ref, pool_chk, atol=CHUNK_KV_ATOL)


def test_prefill_chunks_random_lengths_token_identical():
    """Seeded random prefix/suffix lengths x chunk sizes — the
    deterministic twin of the hypothesis property in test_property.py
    (which is skipped where hypothesis is not installed)."""
    rng = np.random.default_rng(0)
    for chunk in (1, 2, 3, 4, 8):
        prefix_lens = rng.integers(0, 9, 3).tolist()
        n_tok = rng.integers(0, 9, 3).tolist()
        _check_chunked_vs_stepwise(prefix_lens, n_tok, chunk,
                                   seed=int(rng.integers(1 << 30)))


def test_prefill_chunks_zero_suffix_and_block_boundary():
    """Regression (ISSUE 6 satellite): zero-length suffix rows must leave
    their resident blocks untouched (writes park in the trash block), and
    rows whose pos0 + n_tokens lands exactly on a block boundary — or past
    capacity — must clamp bitwise like the stepwise scan."""
    cfg = reduced_config(get_config("smollm-360m"))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    bs, cap = 4, 16
    # row 0: 8+8 = 16 ends exactly at capacity (block boundary);
    # row 1: 12+6 = 18 overruns capacity -> capacity-1 clamp;
    # row 2: zero-length suffix on a staged 6-token prefix
    pool, tables, sfx, pos0, n_tok = _staged_suffix_state(
        cfg, ctx, params, prefix_lens=[8, 12, 6], n_tok=[8, 6, 0],
        bs=bs, cap=cap)
    tmax = sfx.shape[1]
    f_ref, pool_ref = model.prefill_loop(
        cfg, params, jax.tree.map(jnp.copy, pool), jnp.asarray(sfx),
        jnp.asarray(pos0), jnp.asarray(n_tok), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, num_steps=tmax,
        capacity=cap)
    f_chk, pool_chk = model.prefill_chunks(
        cfg, params, jax.tree.map(jnp.copy, pool), jnp.asarray(sfx),
        jnp.asarray(pos0), jnp.asarray(n_tok), ctx,
        block_tables=jnp.asarray(tables), block_size=bs, chunk=4,
        num_steps=2, capacity=cap)
    np.testing.assert_array_equal(np.asarray(f_chk)[:2],
                                  np.asarray(f_ref)[:2])
    _assert_live_blocks_equal(pool_ref, pool_chk)
    # the dead row's resident blocks are bitwise untouched by both paths:
    # its writes went to trash block 0, never to a live block
    row2 = tables[2][tables[2] > 0]
    for before, after in ((pool, pool_ref), (pool, pool_chk)):
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            ax = a.ndim - 4
            np.testing.assert_array_equal(
                np.take(np.asarray(a), row2, axis=ax),
                np.take(np.asarray(b), row2, axis=ax))


def test_mixed_loop_matches_split_prefill_then_decode():
    """mixed_loop — ONE scan fusing the decode window with joining rows'
    chunked suffix prefill — must emit bitwise what the split path emits
    (prefill_chunks, then decode_loop), across ragged decode budgets
    including an inactive slot, because the two tiles touch disjoint
    blocks."""
    cfg = reduced_config(get_config("smollm-360m"))
    ctx = RunContext()
    params = model.init(cfg, KEY)
    bs, cap, W, C = 4, 16, 4, 2
    cache, tables, tok, pos = _paged_decode_state(
        cfg, ctx, params, prompt_lens=[3, 5, 1, 4, 5, 2], block_size=bs,
        capacity=cap)
    dec_tbl, sfx_tbl = tables[:4], tables[4:]
    budgets = np.array([W, 2, 0, W], np.int32)
    rng = np.random.default_rng(3)
    sfx = rng.integers(0, cfg.vocab_size, (2, 6), dtype=np.int32)
    spos = np.array([5, 2], np.int32)
    sn = np.array([6, 3], np.int32)             # ragged; tmax 6 -> 3 chunks
    n_chunks = -(-sfx.shape[1] // C)

    f_ref, pool1 = model.prefill_chunks(
        cfg, params, jax.tree.map(jnp.copy, cache), jnp.asarray(sfx),
        jnp.asarray(spos), jnp.asarray(sn), ctx,
        block_tables=jnp.asarray(sfx_tbl), block_size=bs, chunk=C,
        num_steps=n_chunks, capacity=cap)
    dec_ref, pool_ref = model.decode_loop(
        cfg, params, pool1, jnp.asarray(tok[:4]), jnp.asarray(pos[:4]),
        jnp.asarray(budgets), ctx, block_tables=jnp.asarray(dec_tbl),
        block_size=bs, num_steps=W, capacity=cap)

    dec_m, f_m, pool_m = model.mixed_loop(
        cfg, params, jax.tree.map(jnp.copy, cache), jnp.asarray(tok[:4]),
        jnp.asarray(pos[:4]), jnp.asarray(budgets), jnp.asarray(sfx),
        jnp.asarray(spos), jnp.asarray(sn), ctx,
        block_tables=jnp.asarray(dec_tbl),
        sfx_tables=jnp.asarray(sfx_tbl), block_size=bs, chunk=C,
        num_steps=max(W, n_chunks), capacity=cap)
    np.testing.assert_array_equal(np.asarray(f_m), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(dec_m)[:, :W],
                                  np.asarray(dec_ref))
    _assert_live_blocks_equal(pool_ref, pool_m)


def test_paged_cache_rejects_non_full_attention():
    """Regression (ISSUE 4 satellite): paged KV requires full attention —
    both guard sites must keep raising a clean NotImplementedError for a
    windowed/recurrent config instead of silently mis-gathering."""
    cfg = reduced_config(get_config("recurrentgemma-2b"))
    assert cfg.window is not None               # local-attention config
    with pytest.raises(NotImplementedError, match="full attention"):
        model.init_paged_cache(cfg, 2, 9, 4)    # models/model.py guard
    # models/blocks.py guard: a decode step handed block tables on a
    # windowed config must refuse at trace time, whatever the cache is
    params = model.init(cfg, KEY)
    cache = model.init_cache(cfg, 2, 8)
    with pytest.raises(NotImplementedError, match="full attention"):
        model.decode_step(cfg, params, cache,
                          jnp.zeros((2, 1), jnp.int32),
                          jnp.zeros((2,), jnp.int32), RunContext(),
                          block_tables=jnp.zeros((2, 2), jnp.int32),
                          block_size=4)


def test_cache_logical_axes_match_cache_structure():
    for arch in list_archs():
        cfg = reduced_config(get_config(arch))
        if cfg.encoder_only:
            continue
        cache = model.abstract_cache(cfg, 2, 8)
        axes = model.cache_logical_axes(cfg)
        ok = jax.tree.map(lambda c, a: len(c.shape) == len(a), cache, axes)
        assert all(jax.tree.leaves(ok)), arch


# --------------------------------------------------------------------------- #
# Speculative decoding: draft_loop / verify_window (ADR-008)
# --------------------------------------------------------------------------- #
def _spec_state(cfg, ctx, params, prompt_lens, bs, cap, seed=11):
    """Stage seeded prompts into a paged pool via one prefill scan.

    Returns (pool, tables, prompts, tok (B,), pos (B,)): the serving state
    right before decoding — ``tok[i]`` is row i's first generated (current,
    KV-unwritten) token at cursor ``pos[i] = len(prompts[i])``.
    """
    slots = len(prompt_lens)
    max_blk = cap // bs
    pool = model.init_paged_cache(cfg, slots, slots * max_blk + 1, bs)
    rng = np.random.default_rng(seed)
    tables = np.zeros((slots, max_blk), np.int32)
    nxt = 1
    for i in range(slots):
        for j in range(max_blk):
            tables[i, j] = nxt
            nxt += 1
    prompts = [rng.integers(0, cfg.vocab_size, ln).astype(np.int32)
               for ln in prompt_lens]
    pre = np.zeros((slots, max(prompt_lens)), np.int32)
    for i, p in enumerate(prompts):
        pre[i, :len(p)] = p
    first, pool = model.prefill_loop(
        cfg, params, pool, jnp.asarray(pre),
        jnp.asarray(np.zeros(slots, np.int32)),
        jnp.asarray(np.asarray(prompt_lens, np.int32)), ctx,
        block_tables=jnp.asarray(tables), block_size=bs,
        num_steps=pre.shape[1], capacity=cap)
    return pool, tables, prompts, np.asarray(first, np.int32), \
        np.asarray(prompt_lens, np.int32)


def _run_spec_rounds(cfg, ctx, params, dcfg, dparams, pool, dpool, tables,
                     hist, tok, pos, budgets, bs, cap, k_max, flip_p, rng):
    """Drive draft_loop + verify_window rounds until every budget drains.

    The draft is an oracle (or a real reduced model when dcfg/dparams
    differ) whose proposals are corrupted with per-token probability
    ``flip_p`` and whose window size is drawn per-row per-round — random
    K, mid-window rejections, and dead rows all fall out of the draw.
    Returns (out per-row token lists, cur, pos, pool).
    """
    slots = len(budgets)
    cur, p, left = tok.copy(), pos.copy(), np.asarray(budgets, np.int32)
    left = left.copy()
    dp = np.zeros((slots,), np.int32)           # draft pool cursor
    out = [[] for _ in range(slots)]
    guard = 0
    while (left > 0).any():
        guard += 1
        assert guard <= 4 * (int(left.max()) + 1), "spec loop diverged"
        live = left > 0
        room = np.maximum(cap - 1 - np.minimum(p, cap - 1), 0)
        k_cap = np.minimum(np.minimum(k_max, left - 1), room)
        k = np.where(live, rng.integers(0, np.maximum(k_cap, 0) + 1), 0)
        k = k.astype(np.int32)
        # --- draft side: catch-up (hist[dp:p]) + k greedy steps ---
        n_c = np.where(live, p - dp, 0).astype(np.int32)
        tc = max(int(n_c.max()), 1)
        ctoks = np.zeros((slots, tc), np.int32)
        for i in range(slots):
            if n_c[i]:
                ctoks[i, :n_c[i]] = hist[i][dp[i]:p[i]]
        drafts, dpool = model.draft_loop(
            dcfg, dparams, dpool, jnp.asarray(ctoks),
            jnp.asarray(np.where(live, dp, 0).astype(np.int32)),
            jnp.asarray(n_c), jnp.asarray(cur[:, None]),
            jnp.asarray(np.where(live, p, 0).astype(np.int32)),
            jnp.asarray(k), ctx, block_tables=jnp.asarray(tables),
            block_size=bs, catchup_steps=tc, num_steps=k_max, capacity=cap)
        drafts = np.asarray(drafts, np.int32)
        flips = rng.random((slots, k_max)) < flip_p
        drafts = np.where(flips, (drafts + 1) % cfg.vocab_size, drafts)
        dp = np.where(live, p + k, dp)
        # --- verify side: one chunked dispatch over k+1 window tokens ---
        x = np.concatenate([cur[:, None], drafts], axis=1)
        n_live = np.where(live, k + 1, 0).astype(np.int32)
        greedy, pool = model.verify_window(
            cfg, params, pool, jnp.asarray(x),
            jnp.asarray(np.where(live, np.minimum(p, cap - 1), 0)),
            jnp.asarray(n_live), ctx, block_tables=jnp.asarray(tables),
            block_size=bs, capacity=cap)
        greedy = np.asarray(greedy, np.int32)
        acc = model.spec_accept(greedy, drafts, np.where(live, k, 0))
        for i in range(slots):
            if live[i]:
                got = greedy[i, :acc[i] + 1].tolist()
                out[i].extend(got)
                hist[i].extend(got)
        emitted = np.where(live, acc + 1, 0).astype(np.int32)
        cur = np.where(live, greedy[np.arange(slots), acc], cur)
        p = np.where(live, np.minimum(p + emitted, cap), p)
        left = left - emitted
        dp = np.where(live, np.minimum(dp, p), dp)
    return out, cur, p, pool


def _check_spec_vs_stepwise(prompt_lens, budgets, k_max, flip_p, seed=11,
                            cap=32, real_draft=False):
    """Full speculative decode (oracle/real draft, random per-round K,
    corrupted proposals) must emit token-identical output to stepwise
    greedy decode — and leave committed KV a continuation can't tell
    apart (stale rejected-position KV is provably never read)."""
    cfg, ctx, params = _chunk_fixture()
    bs = 4
    budgets = np.asarray(budgets, np.int32)
    if budgets.max() == 0:
        budgets = budgets.copy()
        budgets[0] = 1                          # at least one live row
    pool, tables, prompts, tok, pos = _spec_state(
        cfg, ctx, params, list(prompt_lens), bs, cap, seed=seed)
    T = int(budgets.max())
    want, pool_ref = _stepwise_decode(
        cfg, ctx, params, jax.tree.map(jnp.copy, pool), tables,
        tok[:, None], pos, budgets, bs, cap, T)

    if real_draft:
        dcfg = draft_config(get_config("smollm-360m"))
        dparams = model.init(dcfg, jax.random.PRNGKey(7))
    else:
        dcfg, dparams = cfg, params             # oracle draft
    slots = len(prompt_lens)
    max_blk = cap // bs
    dpool = model.init_paged_cache(dcfg, slots, slots * max_blk + 1, bs)
    hist = [p.tolist() + [int(tok[i])] for i, p in enumerate(prompts)]
    rng = np.random.default_rng(seed + 1)
    out, cur, p, pool_spec = _run_spec_rounds(
        cfg, ctx, params, dcfg, dparams, jax.tree.map(jnp.copy, pool),
        dpool, tables, hist, tok, pos, budgets, bs, cap, k_max, flip_p, rng)

    for i in range(slots):
        np.testing.assert_array_equal(
            np.asarray(out[i], np.int32), want[i, :budgets[i]],
            err_msg=f"slot {i} speculative stream != stepwise greedy")
    # cursors and current tokens line up with the stepwise endpoint
    p_ref = np.minimum(pos + budgets, cap)
    np.testing.assert_array_equal(p, p_ref)
    # committed KV is intact: a plain stepwise continuation from the same
    # (token, cursor) state must match on both pools — this reads every
    # committed position and causally masks the stale rejected tail
    ext = np.minimum(np.maximum(cap - p, 0), 3).astype(np.int32)
    if ext.max() > 0:
        cont_ref, _ = _stepwise_decode(
            cfg, ctx, params, pool_ref, tables, cur[:, None], p, ext,
            bs, cap, int(ext.max()))
        cont_spec, _ = _stepwise_decode(
            cfg, ctx, params, pool_spec, tables, cur[:, None], p, ext,
            bs, cap, int(ext.max()))
        np.testing.assert_array_equal(cont_spec, cont_ref)


def test_verify_window_token_identical_sweep():
    """Deterministic twin of the hypothesis property (PR 6 pattern):
    seeded sweeps over draft quality — oracle-perfect (full accepts),
    always-wrong (every window rejects at position 0, degenerating to
    per-token decode), and mid-window rejections — plus a dead row and
    ragged budgets."""
    for flip_p, seed in [(0.0, 3), (1.0, 5), (0.35, 7), (0.5, 11)]:
        _check_spec_vs_stepwise(prompt_lens=[3, 5, 1], budgets=[6, 4, 0],
                                k_max=3, flip_p=flip_p, seed=seed)


def test_verify_window_capacity_clamp_matches_stepwise():
    """Windows shrink to k=0 at the capacity edge (no pinned-write
    collapse is ever allowed inside a verify window), matching the
    stepwise clamp bitwise."""
    _check_spec_vs_stepwise(prompt_lens=[6, 4], budgets=[8, 8], k_max=3,
                            flip_p=0.2, seed=13, cap=8)


def test_real_reduced_draft_model_is_still_lossless():
    """A genuinely different (randomly initialized, architecturally
    smaller) draft model mostly disagrees with the target — acceptance
    collapses — but the emitted stream must STILL be token-identical:
    verification makes draft quality a pure performance knob."""
    _check_spec_vs_stepwise(prompt_lens=[4, 2], budgets=[5, 3], k_max=3,
                            flip_p=0.0, seed=17, real_draft=True)
