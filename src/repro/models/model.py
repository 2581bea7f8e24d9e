"""Unified model: specs, init, forward (train/prefill), decode step, loss.

Layer stacks are ``lax.scan``-over-groups with stacked params: HLO size and
compile time are independent of depth (essential for 512-device dry-runs).
A "group" is one repetition of ``cfg.block_pattern``; layers left over after
the last full group ("rest") are applied unscanned.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.context import RunContext
from repro.models.layers import apply_norm, rope_tables
from repro.models.spec import (ParamSpec, abstract_params, init_params,
                               logical_axes, param_count, stack_specs)

_AUX_COEF = 0.01


def constrain(x: jax.Array, ctx: RunContext, *trailing) -> jax.Array:
    """with_sharding_constraint helper: batch dim over dp axes + trailing
    logical entries given as mesh-axis names (or None).  GSPMD's propagation
    through scan loops is weak; these pins keep activations batch-sharded.
    """
    if ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = ctx.dp_spec()
    dp_entry = dp if x.shape[0] % ctx.dp_size == 0 else None
    entries = [dp_entry]
    for size, name in zip(x.shape[1:], trailing):
        if name is not None and size % ctx.mesh.shape[name] == 0:
            entries.append(name)
        else:
            entries.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*entries)))


# --------------------------------------------------------------------------- #
# Grouping
# --------------------------------------------------------------------------- #
def grouping(cfg: ModelConfig):
    """(pattern, n_groups, rest_kinds)."""
    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    rest = cfg.layer_kinds()[n_groups * len(pat):]
    return pat, n_groups, rest


# --------------------------------------------------------------------------- #
# Specs / init
# --------------------------------------------------------------------------- #
def param_specs(cfg: ModelConfig) -> Dict:
    d, v = cfg.d_model, cfg.vocab_size
    pat, n_groups, rest = grouping(cfg)
    sp: Dict = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), fan_in=d),
        "final_norm": blocks.norm_specs(cfg),
        "layers": {
            "stack": {f"b{i}": stack_specs(blocks.block_specs(cfg, kind),
                                           n_groups)
                      for i, kind in enumerate(pat)},
            "rest": {f"r{i}": blocks.block_specs(cfg, kind)
                     for i, kind in enumerate(rest)},
        },
    }
    if not cfg.tie_embeddings:
        sp["head"] = ParamSpec((d, v), ("embed", "vocab"))
    if cfg.frontend is not None:
        sp["frontend"] = {"proj": ParamSpec((d, d), ("embed", "embed_out"))}
    # honor cfg.dtype: bf16-default specs follow the config (explicit fp32
    # specs — norms stats, decay params — stay fp32)
    if cfg.dtype != "bfloat16":
        target = jnp.dtype(cfg.dtype)
        sp = jax.tree.map(
            lambda s: dataclasses.replace(s, dtype=target)
            if s.dtype == jnp.bfloat16 else s,
            sp, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sp


def init(cfg: ModelConfig, key: jax.Array):
    return init_params(param_specs(cfg), key)


def init_abstract(cfg: ModelConfig):
    return abstract_params(param_specs(cfg))


def param_logical_axes(cfg: ModelConfig):
    return logical_axes(param_specs(cfg))


def n_params(cfg: ModelConfig) -> int:
    return param_count(param_specs(cfg))


def n_active_params(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k of n_experts expert params)."""
    if not cfg.is_moe:
        return n_params(cfg)
    total = n_params(cfg)
    specs = param_specs(cfg)
    expert_leaves = [
        s for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
        if len(s.shape) >= 3 and cfg.n_experts in s.shape[:2] and s.shape[-1] != cfg.n_experts
    ]
    expert_total = sum(int(np.prod(s.shape)) for s in expert_leaves)
    return total - expert_total + expert_total * cfg.top_k // cfg.n_experts


# --------------------------------------------------------------------------- #
# Cache
# --------------------------------------------------------------------------- #
def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> Dict:
    """Zero decode cache, stacked to match the scan grouping."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    pat, n_groups, rest = grouping(cfg)

    def stacked(kind):
        one = blocks.init_block_cache(cfg, kind, batch, capacity, dtype)
        return jax.tree.map(
            lambda a: jnp.zeros((n_groups,) + a.shape, a.dtype), one)

    return {
        "stack": {f"b{i}": stacked(kind) for i, kind in enumerate(pat)},
        "rest": {f"r{i}": blocks.init_block_cache(cfg, kind, batch, capacity,
                                                  dtype)
                 for i, kind in enumerate(rest)},
    }


def abstract_cache(cfg: ModelConfig, batch: int, capacity: int):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, capacity))


def cache_axes(cfg: ModelConfig):
    """Locate each cache leaf's (batch_axis, capacity_axis) by shape diffing.

    Returns two trees matching :func:`init_cache`'s structure: the axis that
    scales with batch, and the axis that scales with capacity (``None`` for
    per-row state leaves such as recurrent hidden states, which have no
    sequence storage).  The paged-KV machinery uses these to treat KV leaves
    as block pools and state leaves as slot-indexed rows, without
    hard-coding any block's cache layout.
    """
    def diff_axis(x, y):
        d = [i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q]
        return d[0] if d else None

    b1, b2 = abstract_cache(cfg, 1, 16), abstract_cache(cfg, 2, 16)
    c1, c2 = abstract_cache(cfg, 1, 8), abstract_cache(cfg, 1, 16)
    return (jax.tree.map(diff_axis, b1, b2), jax.tree.map(diff_axis, c1, c2))


def init_paged_cache(cfg: ModelConfig, max_slots: int, num_blocks: int,
                     block_size: int, dtype=None) -> Dict:
    """Zero paged decode cache: a block pool plus per-slot state rows.

    Attention KV leaves become head-major physical block pools — the
    contiguous (B, capacity, Hkv, D) storage is replaced by (num_blocks,
    Hkv, block_size, D), so one block of one KV head is a contiguous
    (block_size, D) tile: the unit the paged kernels stage into VMEM, and a
    block shape the TPU compiler accepts.  Which blocks belong to which
    slot is the caller's block table.  Leaves with no capacity axis
    (recurrent state) keep one row per slot: (max_slots, ...).  Block id 0
    is conventionally the trash block that inactive slots write into;
    allocators should never hand it out.
    """
    if cfg.window is not None:
        raise NotImplementedError("paged KV cache requires full attention "
                                  "(cfg.window=None)")
    pool = init_cache(cfg, num_blocks, block_size, dtype)
    state = init_cache(cfg, max_slots, 1, dtype)
    _, cap_ax = cache_axes(cfg)
    return jax.tree.map(
        lambda kv, st, ax: st if ax is None else jnp.swapaxes(kv, ax, ax + 1),
        pool, state, cap_ax)


def paged_block_axes(cfg: ModelConfig):
    """Each :func:`init_paged_cache` leaf's in-block token axis: the
    contiguous cache's capacity axis, moved behind the KV-head axis
    (``None`` for per-slot state rows)."""
    _, cap_ax = cache_axes(cfg)
    return jax.tree.map(lambda ax: ax + 1, cap_ax)


def cache_logical_axes(cfg: ModelConfig):
    """Logical axes tree matching ``init_cache`` (leading layer-stack dim)."""
    pat, _, rest = grouping(cfg)

    def stacked(kind):
        one = blocks.block_cache_axes(cfg, kind)
        return jax.tree.map(lambda a: ("layers",) + a, one,
                            is_leaf=lambda x: isinstance(x, tuple)
                            and all(isinstance(e, (str, type(None)))
                                    for e in x))

    return {
        "stack": {f"b{i}": stacked(kind) for i, kind in enumerate(pat)},
        "rest": {f"r{i}": blocks.block_cache_axes(cfg, kind)
                 for i, kind in enumerate(rest)},
    }


# --------------------------------------------------------------------------- #
# Input embedding (text / audio-stub / vision-stub frontends)
# --------------------------------------------------------------------------- #
def embed_inputs(cfg: ModelConfig, params: Dict, batch: Dict):
    """Returns (x, positions, prefix_len)."""
    dtype = jnp.dtype(cfg.dtype)
    emb = params["embed"]

    def tok_embed(tokens):
        x = jnp.take(emb, tokens, axis=0).astype(dtype)
        if cfg.scale_embeddings:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
        return x

    if cfg.frontend == "audio":
        x = jnp.einsum("bsd,de->bse", batch["frames"].astype(dtype),
                       params["frontend"]["proj"]).astype(dtype)
        prefix_len = 0
    elif cfg.frontend == "vision":
        patches = jnp.einsum("bpd,de->bpe", batch["patches"].astype(dtype),
                             params["frontend"]["proj"]).astype(dtype)
        x = jnp.concatenate([patches, tok_embed(batch["tokens"])], axis=1)
        prefix_len = patches.shape[1]
    else:
        x = tok_embed(batch["tokens"])
        prefix_len = 0
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return x, positions, prefix_len


def unembed(cfg: ModelConfig, params: Dict, x: jax.Array,
            ctx: RunContext) -> jax.Array:
    x = apply_norm(x, params["final_norm"], cfg.norm_type)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"],
                            preferred_element_type=jnp.float32)
    logits = constrain(logits, ctx, None, ctx.model_axis)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# --------------------------------------------------------------------------- #
# Stack application
# --------------------------------------------------------------------------- #
def _remat_wrap(fn, ctx: RunContext, mode: str):
    if mode != "train" or ctx.remat == "none":
        return fn
    if ctx.remat == "dots":
        policy = jax.checkpoint_policies.dots_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def apply_stack(cfg: ModelConfig, params: Dict, x: jax.Array,
                ctx: RunContext, rope, cache: Optional[Dict], mode: str,
                prefix_len: int, pos, cache_capacity: int = 0,
                block_tables=None, block_size: int = 0):
    """Runs all layers. Returns (x, new_cache, aux)."""
    pat, n_groups, rest = grouping(cfg)
    want_cache = cache is not None or mode == "prefill"

    seq_ax = ctx.model_axis if (ctx.mesh is not None and ctx.zero_sp) else None

    def group_body(carry, xs):
        xc, aux = carry
        xc = constrain(xc, ctx, seq_ax, None)
        layer_params, layer_cache = xs
        new_caches = {}
        for i, kind in enumerate(pat):
            c_i = None if layer_cache is None else layer_cache[f"b{i}"]
            xc, nc, a = blocks.block_apply(kind, layer_params[f"b{i}"], xc,
                                           cfg, ctx, rope, c_i, mode,
                                           prefix_len, pos, cache_capacity,
                                           block_tables, block_size)
            if want_cache:
                new_caches[f"b{i}"] = nc
        return (xc, aux + a), (new_caches if want_cache else None)

    body = _remat_wrap(group_body, ctx, mode)
    aux0 = jnp.zeros((), jnp.float32)
    cache_stack = None if cache is None else cache["stack"]
    if n_groups > 0:
        (x, aux), new_stack = jax.lax.scan(
            body, (x, aux0), (params["layers"]["stack"], cache_stack),
            unroll=n_groups if ctx.scan_unroll else 1)
    else:
        aux, new_stack = aux0, None

    new_rest = {}
    for i, kind in enumerate(rest):
        c_i = None if cache is None else cache["rest"][f"r{i}"]
        x, nc, a = blocks.block_apply(kind, params["layers"]["rest"][f"r{i}"],
                                      x, cfg, ctx, rope, c_i, mode,
                                      prefix_len, pos, cache_capacity,
                                      block_tables, block_size)
        aux = aux + a
        if want_cache:
            new_rest[f"r{i}"] = nc
    new_cache = {"stack": new_stack, "rest": new_rest} if want_cache else None
    return x, new_cache, aux


# --------------------------------------------------------------------------- #
# Losses
# --------------------------------------------------------------------------- #
def _ce_vocab_sharded(logits: jax.Array, targets: jax.Array,
                      ctx: RunContext) -> jax.Array:
    """Per-token CE with the vocab dim sharded over the model axis.

    A plain take_along_axis over a sharded vocab makes GSPMD all-gather the
    full logits (e.g. 13 GiB/dev for smollm train_4k); inside shard_map each
    shard reduces its local vocab slice and three scalar-ish psums combine.
    """
    from jax.sharding import PartitionSpec as P
    m = ctx.model_axis
    b = logits.shape[0]
    dp = ctx.dp_spec() if b % ctx.dp_size == 0 else None

    def body(lg, tg):
        lg = lg.astype(jnp.float32)
        v_loc = lg.shape[-1]
        off = jax.lax.axis_index(m) * v_loc
        # stop_gradient: lse is shift-invariant, so treating the max as a
        # constant yields exact gradients (and pmax has no JVP rule —
        # the stop must sit *inside* so pmax never sees a tangent)
        lmax = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(lg, axis=-1)), m)
        z = jnp.exp(lg - lmax[..., None])
        denom = jax.lax.psum(jnp.sum(z, axis=-1), m)
        idx = tg - off
        ok = (idx >= 0) & (idx < v_loc)
        safe = jnp.clip(idx, 0, v_loc - 1)
        ll_loc = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(ok, ll_loc, 0.0), m)
        return jnp.log(denom) + lmax - ll

    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dp, None, m), P(dp, None)),
        out_specs=P(dp, None), check_vma=False)(logits, targets)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array], chunk: int = 0,
                  ctx: Optional[RunContext] = None):
    """Stable CE over (possibly vocab-sharded) logits. logits: (B,S,V) f32."""
    if (ctx is not None and ctx.mesh is not None
            and logits.shape[-1] % ctx.model_size == 0):
        losses = _ce_vocab_sharded(logits, targets, ctx)
        if mask is None:
            return jnp.mean(losses)
        mask = mask.astype(jnp.float32)
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    def ce(lg, tg):
        lg = lg.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
        return lse - ll

    if chunk and logits.shape[1] % chunk == 0 and logits.shape[1] > chunk:
        b, s, v = logits.shape
        n = s // chunk
        lg = logits.reshape(b, n, chunk, v).swapaxes(0, 1)
        tg = targets.reshape(b, n, chunk).swapaxes(0, 1)
        losses = jax.lax.map(lambda args: ce(*args), (lg, tg))
        losses = losses.swapaxes(0, 1).reshape(b, s)
    else:
        losses = ce(logits, targets)
    if mask is None:
        return jnp.mean(losses)
    mask = mask.astype(jnp.float32)
    return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------------------- #
# Public entry points
# --------------------------------------------------------------------------- #
def forward(cfg: ModelConfig, params: Dict, batch: Dict, ctx: RunContext,
            mode: str = "train", cache_capacity: int = 0):
    """mode="train" -> (loss, metrics); mode="prefill" -> (last_logits, cache)."""
    x, positions, prefix_len = embed_inputs(cfg, params, batch)
    seq_ax = ctx.model_axis if (ctx.mesh is not None and ctx.zero_sp) else None
    x = constrain(x, ctx, seq_ax, None)
    rope = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    x, new_cache, aux = apply_stack(cfg, params, x, ctx, rope, None, mode,
                                    prefix_len, pos=None,
                                    cache_capacity=cache_capacity)
    if mode == "prefill":
        logits = unembed(cfg, params, x[:, -1:], ctx)
        return logits[:, 0], new_cache
    logits = unembed(cfg, params, x, ctx)
    if cfg.frontend == "vision":
        # loss over the text suffix only
        logits = logits[:, prefix_len:]
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    loss = cross_entropy(logits, targets, mask, ctx.loss_chunk, ctx)
    total = loss + _AUX_COEF * aux
    return total, {"loss": loss, "aux": aux}


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict,
                tokens: jax.Array, pos: jax.Array, ctx: RunContext,
                block_tables: Optional[jax.Array] = None,
                block_size: int = 0):
    """One decode step. tokens: (B,1) int32.

    ``pos`` is the decode cursor: a scalar int32 when the whole batch shares
    one position (contiguous cohort cache), or a (B,) int32 vector of
    *per-slot* cursors when ``block_tables`` (B, M) maps each row onto a
    paged KV block pool (leaves (num_blocks, Hkv, block_size, D) instead of
    (B, capacity, Hkv, D)).  Per-slot cursors are what let a late arrival
    join an in-flight batch: rows no longer share a position.

    Returns (logits (B,V), new_cache).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
    b = x.shape[0]
    if jnp.ndim(pos) == 0:
        positions = jnp.broadcast_to(pos[None, None], (b, 1))
    else:
        positions = pos[:, None]
    rope = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    x, new_cache, _ = apply_stack(cfg, params, x, ctx, rope, cache, "decode",
                                  prefix_len=0, pos=pos,
                                  block_tables=block_tables,
                                  block_size=block_size)
    logits = unembed(cfg, params, x, ctx)
    return logits[:, 0], new_cache


def decode_loop(cfg: ModelConfig, params: Dict, cache: Dict,
                tokens: jax.Array, pos: jax.Array, steps_left: jax.Array,
                ctx: RunContext, *, block_tables: jax.Array,
                block_size: int, num_steps: int, capacity: int):
    """Fused multi-token decode: ``num_steps`` greedy steps in ONE dispatch.

    A ``lax.scan`` over T :func:`decode_step` calls, entirely on device —
    greedy (argmax) sampling, per-slot cursor advance, and the block-table-
    indexed KV writes all happen inside the scan, so the host↔device
    round-trip cost drops from one-per-token to one-per-window.

    tokens: (B, 1) int32 — each slot's current token; pos: (B,) int32
    per-slot cursors (``kv.pos`` convention: may equal ``capacity``);
    steps_left: (B,) int32 — tokens still to emit per slot this window.
    A row whose ``steps_left`` is exhausted (or 0: an empty slot) is *dead*:
    its table row and cursor are masked to 0 so its KV write lands in the
    trash block (block 0) and its emitted token freezes — the host frees
    the slot's real blocks only at the window boundary, so mid-window
    completions can never corrupt live KV.  Cursor advance clamps exactly
    like the per-token serving path (write position pins to ``capacity-1``
    past the end), which is what makes the window token-identical to T
    calls of :func:`decode_step`.

    Returns (tokens_out (B, T) int32, new_cache); row i of ``tokens_out``
    holds the token emitted at each step (frozen once the row dies).
    """
    tok0 = tokens[:, 0].astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    steps_left = steps_left.astype(jnp.int32)

    def step(carry, t):
        cache, tok, cur = carry
        live = t < steps_left
        eff_tables = jnp.where(live[:, None], tables, 0)
        eff_pos = jnp.where(live, jnp.minimum(cur, capacity - 1), 0)
        logits, cache = decode_step(cfg, params, cache, tok[:, None],
                                    eff_pos, ctx, block_tables=eff_tables,
                                    block_size=block_size)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        nxt = jnp.where(live, nxt, tok)
        cur = jnp.where(live, jnp.minimum(cur + 1, capacity), cur)
        return (cache, nxt, cur), nxt

    with jax.named_scope("decode"):
        (cache, _, _), toks = jax.lax.scan(
            step, (cache, tok0, pos.astype(jnp.int32)),
            jnp.arange(num_steps, dtype=jnp.int32))
    return jnp.swapaxes(toks, 0, 1), cache


def prefill_loop(cfg: ModelConfig, params: Dict, cache: Dict,
                 tokens: jax.Array, pos0: jax.Array, n_tokens: jax.Array,
                 ctx: RunContext, *, block_tables: jax.Array,
                 block_size: int, num_steps: int, capacity: int):
    """Suffix prefill over a paged pool: teacher-forced decode scan.

    The restore / prefix-hit path of the copy-on-write prefix cache
    (docs/architecture.md ADR-003): a row whose prompt prefix is already
    resident in cached KV blocks only needs its *uncached suffix* written —
    starting from a per-row offset ``pos0[i]``, which a batched
    ``forward(mode="prefill")`` cannot do (it always starts at position 0).
    This runs the suffix through :func:`decode_step` under one ``lax.scan``
    dispatch: step ``t`` feeds the given token ``tokens[i, t]`` (teacher
    forcing — no sampling), writes its KV at position ``pos0[i] + t``
    through the row's block table, and attends over the full context so
    far — cached prefix blocks included.

    tokens: (B, T) int32 suffix tokens, rows padded past ``n_tokens[i]``;
    pos0: (B,) int32 first uncached position per row (the cached-prefix
    length); n_tokens: (B,) int32 live suffix length per row (0 = inactive
    pad row: its writes park in the trash block, like ``decode_loop``).

    Returns (first_tokens (B,), new_cache): ``first_tokens[i]`` is the
    greedy next token after row i's last suffix position — the row's first
    generated token, exactly what a full prefill's final logits yield.
    """
    tables = block_tables.astype(jnp.int32)
    n_tokens = n_tokens.astype(jnp.int32)
    pos0 = pos0.astype(jnp.int32)
    first0 = jnp.zeros((tokens.shape[0],), jnp.int32)

    def step(carry, xs):
        cache, first = carry
        t, tok_t = xs
        live = t < n_tokens
        eff_tables = jnp.where(live[:, None], tables, 0)
        eff_pos = jnp.where(live, jnp.minimum(pos0 + t, capacity - 1), 0)
        logits, cache = decode_step(cfg, params, cache, tok_t[:, None],
                                    eff_pos, ctx, block_tables=eff_tables,
                                    block_size=block_size)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        first = jnp.where(t == n_tokens - 1, nxt, first)
        return (cache, first), None

    xs = (jnp.arange(num_steps, dtype=jnp.int32),
          jnp.swapaxes(tokens.astype(jnp.int32), 0, 1))
    (cache, first), _ = jax.lax.scan(step, (cache, first0), xs)
    return first, cache


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Whether :func:`prefill_chunks` covers this architecture.

    Chunked prefill needs every layer to be full (windowless) attention:
    recurrent kinds (rglru/rwkv) carry sequential per-token state a chunk
    cannot parallelize, and sliding windows are rejected by the paged pool
    anyway.  Callers fall back to the stepwise :func:`prefill_loop` scan.
    """
    return cfg.window is None and \
        all(k == "attn" for k in cfg.layer_kinds())


def chunk_step(cfg: ModelConfig, params: Dict, cache: Dict,
               tokens: jax.Array, pos0: jax.Array, n_live: jax.Array,
               ctx: RunContext, *, block_tables: jax.Array,
               block_size: int, capacity: int):
    """One C-token chunk of suffix prefill per row, in ONE model pass.

    The chunked sibling of :func:`decode_step`: tokens (B, C) int32 are a
    chunk of each row's uncached suffix starting at cursor ``pos0[i]``, of
    which the first ``n_live[i]`` (0..C) are real.  Every layer scatters
    the chunk's K/V into the row's paged blocks through ``block_tables``
    and attends over the resident prefix plus the chunk (causal) — see the
    ``mode="chunk"`` branch of ``blocks.attn_apply``.

    Returns (last_logits (B, V), new_cache): the logits after each row's
    *last live* token (rows with ``n_live == 0`` yield garbage the caller
    masks out).  Position embedding clamps to ``capacity - 1`` exactly like
    the stepwise scan, so live-token computation is bitwise identical.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
    c = x.shape[1]
    pos0 = pos0.astype(jnp.int32)
    n_live = n_live.astype(jnp.int32)
    positions = jnp.minimum(pos0[:, None] + jnp.arange(c), capacity - 1)
    rope = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    x, new_cache, _ = apply_stack(cfg, params, x, ctx, rope, cache, "chunk",
                                  prefix_len=0, pos=(pos0, n_live),
                                  cache_capacity=capacity,
                                  block_tables=block_tables,
                                  block_size=block_size)
    idx = jnp.clip(n_live - 1, 0, c - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)   # (B, 1, D)
    logits = unembed(cfg, params, x_last, ctx)
    return logits[:, 0], new_cache


def prefill_chunks(cfg: ModelConfig, params: Dict, cache: Dict,
                   tokens: jax.Array, pos0: jax.Array, n_tokens: jax.Array,
                   ctx: RunContext, *, block_tables: jax.Array,
                   block_size: int, chunk: int, num_steps: int,
                   capacity: int):
    """Chunked suffix prefill: :func:`prefill_loop` at C tokens per step.

    Same contract as :func:`prefill_loop` — tokens (B, T) suffix rows,
    per-row start cursors ``pos0`` and live lengths ``n_tokens`` — but the
    scan advances ``chunk`` tokens per step via :func:`chunk_step`, so a
    T-token suffix costs ⌈T/chunk⌉ sequential steps instead of T.  Token-
    identical to the stepwise scan (greedy first token per row), including
    the trash-block parking of dead rows and the ``capacity - 1`` clamp.

    ``num_steps`` is the number of *chunk* steps (⌈T_pad/chunk⌉); tokens
    are padded on device to ``num_steps * chunk`` columns.
    """
    tables = block_tables.astype(jnp.int32)
    n_tokens = n_tokens.astype(jnp.int32)
    pos0 = pos0.astype(jnp.int32)
    toks = tokens.astype(jnp.int32)
    pad = num_steps * chunk - toks.shape[1]
    if pad > 0:
        toks = jnp.pad(toks, ((0, 0), (0, pad)))
    first0 = jnp.zeros((toks.shape[0],), jnp.int32)

    def step(carry, t):
        cache, first = carry
        base = t * chunk
        tok_c = jax.lax.dynamic_slice_in_dim(toks, base, chunk, axis=1)
        n_live = jnp.clip(n_tokens - base, 0, chunk)
        eff_tables = jnp.where((n_live > 0)[:, None], tables, 0)
        logits, cache = chunk_step(cfg, params, cache, tok_c, pos0 + base,
                                   n_live, ctx, block_tables=eff_tables,
                                   block_size=block_size, capacity=capacity)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        done_here = (n_tokens > base) & (n_tokens <= base + chunk)
        first = jnp.where(done_here, nxt, first)
        return (cache, first), None

    with jax.named_scope("prefill_chunk"):
        (cache, first), _ = jax.lax.scan(
            step, (cache, first0), jnp.arange(num_steps, dtype=jnp.int32))
    return first, cache


def mixed_loop(cfg: ModelConfig, params: Dict, cache: Dict,
               tokens: jax.Array, pos: jax.Array, steps_left: jax.Array,
               sfx_tokens: jax.Array, sfx_pos0: jax.Array,
               sfx_n: jax.Array, ctx: RunContext, *,
               block_tables: jax.Array, sfx_tables: jax.Array,
               block_size: int, chunk: int, num_steps: int, capacity: int):
    """Unified mixed prefill/decode engine step: ONE scan, ONE dispatch.

    Fuses a :func:`decode_loop` window over the decode cohort (tokens
    (S, 1) / pos / steps_left / block_tables, exactly decode_loop's
    contract) with :func:`prefill_chunks` over joining rows (sfx_tokens
    (J, T) / sfx_pos0 / sfx_n / sfx_tables), so a mid-flight join or
    preemption restore never stalls the decode cohort behind a separate
    prefill dispatch (docs/architecture.md ADR-005).  Each scan step runs
    the pending prefill chunk first, then the decode step — matching the
    serial order (prefill_into -> suffix scan -> decode window) the split
    path executes, over *disjoint* physical blocks: suffix rows write only
    their own freshly-allocated blocks, so the decode tile's inputs are
    bitwise identical to the split path's.

    Prefill rows take no part in sampling (their ``firsts`` come from the
    teacher-forced chunk logits); decode rows take no part in chunk writes.
    ``num_steps`` covers the longer of the two tiles: a tile past its end
    runs dead (trash-block writes, frozen tokens).

    Returns (tokens_out (S, num_steps), firsts (J,), new_cache).
    """
    tok0 = tokens[:, 0].astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    steps_left = steps_left.astype(jnp.int32)
    stables = sfx_tables.astype(jnp.int32)
    sfx_n = sfx_n.astype(jnp.int32)
    sfx_pos0 = sfx_pos0.astype(jnp.int32)
    stoks = sfx_tokens.astype(jnp.int32)
    pad = num_steps * chunk - stoks.shape[1]
    if pad > 0:
        stoks = jnp.pad(stoks, ((0, 0), (0, pad)))
    first0 = jnp.zeros((stoks.shape[0],), jnp.int32)

    def step(carry, t):
        cache, tok, cur, first = carry
        with jax.named_scope("prefill_chunk"):     # joining rows
            base = t * chunk
            tok_c = jax.lax.dynamic_slice_in_dim(stoks, base, chunk, axis=1)
            n_live = jnp.clip(sfx_n - base, 0, chunk)
            eff_stables = jnp.where((n_live > 0)[:, None], stables, 0)
            logits_c, cache = chunk_step(cfg, params, cache, tok_c,
                                         sfx_pos0 + base, n_live, ctx,
                                         block_tables=eff_stables,
                                         block_size=block_size,
                                         capacity=capacity)
            nxt_c = jnp.argmax(logits_c, -1).astype(jnp.int32)
            done_here = (sfx_n > base) & (sfx_n <= base + chunk)
            first = jnp.where(done_here, nxt_c, first)
        with jax.named_scope("decode"):            # resident cohort
            live = t < steps_left
            eff_tables = jnp.where(live[:, None], tables, 0)
            eff_pos = jnp.where(live, jnp.minimum(cur, capacity - 1), 0)
            logits, cache = decode_step(cfg, params, cache, tok[:, None],
                                        eff_pos, ctx,
                                        block_tables=eff_tables,
                                        block_size=block_size)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            nxt = jnp.where(live, nxt, tok)
            cur = jnp.where(live, jnp.minimum(cur + 1, capacity), cur)
        return (cache, nxt, cur, first), nxt

    (cache, _, _, first), toks = jax.lax.scan(
        step, (cache, tok0, pos.astype(jnp.int32), first0),
        jnp.arange(num_steps, dtype=jnp.int32))
    return jnp.swapaxes(toks, 0, 1), first, cache


def verify_window(cfg: ModelConfig, params: Dict, cache: Dict,
                  tokens: jax.Array, pos0: jax.Array, n_live: jax.Array,
                  ctx: RunContext, *, block_tables: jax.Array,
                  block_size: int, capacity: int):
    """Speculative verification: score a K+1 token window in ONE dispatch.

    The target-model half of cross-tier speculative decoding
    (docs/architecture.md ADR-008).  Row i of ``tokens`` (B, C) is
    ``[t0, d_1 .. d_k, pad...]`` — the slot's current token followed by
    ``k_i`` draft proposals — of which the first ``n_live[i] = k_i + 1``
    are fed, teacher-forced, at positions ``pos0[i] .. pos0[i]+k_i``
    through the ``chunk_step`` machinery (one chunked model pass: paged
    KV writes through ``block_tables``, per-row variable-length causal
    masking in the GQA-fused ``paged_prefill`` kernel).  Unlike
    ``chunk_step`` it unembeds EVERY position, returning the greedy token
    grid (B, C): ``greedy[i, j]`` is the target's next token after
    feeding ``tokens[i, j]`` — bitwise the same computation as ``j+1``
    stepwise :func:`decode_step` calls, because chunk-mode attention is
    write-then-attend with the same ``capacity - 1`` clamp.

    Acceptance happens on the host (:func:`spec_accept`): with
    ``a = accepts[i]`` draft tokens accepted, the emitted tokens are
    ``greedy[i, :a+1]`` (each accepted draft token equals the greedy
    token before it, so the greedy row IS the decoded continuation), the
    new current token is ``greedy[i, a]``, and the cursor advances by
    ``a + 1``.  Rejected positions ``pos0+a+1 .. pos0+k`` hold stale KV:
    harmless, because every later dispatch either overwrites a position
    before attending to it (decode and chunk modes both write first) or
    causally masks it (``k_pos <= pos0 + q``), exactly the chunked-
    prefill containment argument of ADR-005.  Callers must clamp
    ``k_i <= capacity - pos0[i] - 1`` so no window write needs the
    ``capacity - 1`` pin (a pinned write would collapse last-live-wins
    instead of last-step-wins and break stepwise equivalence);
    ``n_live = 0`` rows are dead (trash-block parking, caller masks).

    Returns (greedy (B, C) int32, new_cache).
    """
    dtype = jnp.dtype(cfg.dtype)
    toks = tokens.astype(jnp.int32)
    x = jnp.take(params["embed"], toks, axis=0).astype(dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
    c = x.shape[1]
    pos0 = pos0.astype(jnp.int32)
    n_live = n_live.astype(jnp.int32)
    eff_tables = jnp.where((n_live > 0)[:, None],
                           block_tables.astype(jnp.int32), 0)
    positions = jnp.minimum(pos0[:, None] + jnp.arange(c), capacity - 1)
    rope = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    x, new_cache, _ = apply_stack(cfg, params, x, ctx, rope, cache, "chunk",
                                  prefix_len=0, pos=(pos0, n_live),
                                  cache_capacity=capacity,
                                  block_tables=eff_tables,
                                  block_size=block_size)
    logits = unembed(cfg, params, x, ctx)                     # (B, C, V)
    return jnp.argmax(logits, -1).astype(jnp.int32), new_cache


def draft_loop(cfg: ModelConfig, params: Dict, cache: Dict,
               ctoks: jax.Array, cpos0: jax.Array, n_ctok: jax.Array,
               tokens: jax.Array, pos: jax.Array, k_live: jax.Array,
               ctx: RunContext, *, block_tables: jax.Array,
               block_size: int, catchup_steps: int, num_steps: int,
               capacity: int):
    """Draft side of speculative decoding: catch-up + K greedy steps.

    Runs on the *draft* model (a reduced-cost config sharing the target's
    vocab) against the draft's own paged pool, indexed by the SAME block
    tables as the target (ADR-008: the draft pool mirrors the target's
    block geometry, so no extra host bookkeeping).  Two phases under one
    jitted dispatch:

    1. **Catch-up** (``catchup_steps > 0``): teacher-force ``ctoks``
       (B, Tc) — committed target tokens the draft has not yet ingested —
       at positions ``cpos0[i] ..`` via :func:`prefill_loop`.  After a
       partial accept this is empty; after a full accept it is one token;
       after admit/restore/migration it replays the whole history.  One
       uniform resync path subsumes every case.
    2. **Draft**: ``k_live[i]`` greedy steps from the current token via
       :func:`decode_loop` (dead rows freeze and park in the trash
       block), emitting the proposals ``verify_window`` scores.

    Returns (drafts (B, num_steps) int32, new_cache).
    """
    if catchup_steps > 0:
        _, cache = prefill_loop(cfg, params, cache, ctoks, cpos0, n_ctok,
                                ctx, block_tables=block_tables,
                                block_size=block_size,
                                num_steps=catchup_steps, capacity=capacity)
    return decode_loop(cfg, params, cache, tokens, pos, k_live, ctx,
                       block_tables=block_tables, block_size=block_size,
                       num_steps=num_steps, capacity=capacity)


def spec_accept(greedy: np.ndarray, drafts: np.ndarray,
                n_spec: np.ndarray) -> np.ndarray:
    """Longest-matching-prefix acceptance rule (host side, numpy).

    greedy: (B, C >= K+1) verify_window output; drafts: (B, K) draft
    proposals; n_spec: (B,) live draft count per row (0..K).  Row i
    accepts ``a`` draft tokens where ``a`` is the length of the longest
    prefix with ``drafts[i, j] == greedy[i, j]`` for all ``j < a``
    (draft token ``d_{j+1}`` is accepted iff it equals the target's
    greedy choice after the previous token).  Lossless by construction:
    emitted tokens ``greedy[i, :a+1]`` are exactly what ``a + 1``
    stepwise greedy decode steps would produce.

    Returns accepts (B,) int: accepted draft-token count per row.
    """
    k = drafts.shape[1]
    m = (np.asarray(greedy)[:, :k] == np.asarray(drafts))
    m &= np.arange(k)[None, :] < np.asarray(n_spec)[:, None]
    return np.cumprod(m, axis=1).sum(axis=1).astype(np.int64)
