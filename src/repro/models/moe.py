"""Mixture-of-Experts layer: top-k routing + sort-based capacity dispatch.

Distribution strategy (DESIGN.md §4):
 - routing (router matmul, top-k, load-balance aux) runs in plain GSPMD land;
 - dispatch/compute/combine runs inside ``shard_map``:
     * EP mode (n_experts divisible by the model axis, e.g. OLMoE 64e/16):
       experts sharded over "model"; each shard dispatches its own experts'
       assignments; one psum over "model" combines.
     * TP mode (Mixtral 8e < 16): every shard holds all experts but only a
       slice of d_ff; psum over "model" after the down-projection.
   Expert weights are additionally FSDP-sharded over "data" on the d_model dim
   and all-gathered (tiled) on entry — backward becomes reduce-scatter.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.context import RunContext
from repro.models.layers import _ACTS
from repro.models.spec import ParamSpec


def moe_specs(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    sp = {
        "router": ParamSpec((d, e), ("embed", "experts_r"), jnp.float32),
        "wi": ParamSpec((e, d, f), ("experts", "embed", "mlp"), fan_in=d),
        "wo": ParamSpec((e, f, d), ("experts", "mlp", "embed"), fan_in=f),
    }
    if cfg.mlp_gated:
        sp["wg"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"), fan_in=d)
    return sp


def _route(x2d: jax.Array, router: jax.Array, cfg: ModelConfig):
    """x2d: (T, D) -> weights (T,k), ids (T,k), aux-loss scalar."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    # Mixtral renormalizes over the top-k; OLMoE does not.
    if cfg.name.startswith("mixtral"):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-9)
    # load-balance auxiliary loss (Switch-style): E * sum_e f_e * p_e
    t = x2d.shape[0]
    counts = jnp.zeros((cfg.n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f_e = counts / (t * cfg.top_k)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(f_e * p_e)
    return weights, ids, aux


def _capacity(t: int, cfg: ModelConfig, factor: float) -> int:
    cap = int(t * cfg.top_k / cfg.n_experts * factor)
    return max(8, -(-cap // 8) * 8)


def _dispatch_compute_combine(x2d, weights, ids, wi, wg, wo, *, cfg: ModelConfig,
                              e_offset, e_local: int, capacity: int):
    """Sort-based capacity dispatch on a single shard.

    x2d: (T, D); ids: (T, k) global expert ids; wi: (e_local, D, F) etc.
    Returns this shard's partial output (T, D).
    """
    t, d = x2d.shape
    k = ids.shape[-1]
    flat_ids = ids.reshape(-1)
    sort_idx = jnp.argsort(flat_ids, stable=True)
    s_ids = flat_ids[sort_idx]
    seg_start = jnp.searchsorted(s_ids, s_ids, side="left")
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - seg_start.astype(jnp.int32)
    local = (s_ids >= e_offset) & (s_ids < e_offset + e_local) \
        & (pos_in_e < capacity)
    b_e = jnp.where(local, s_ids - e_offset, e_local)      # OOB row -> dropped
    b_c = jnp.where(local, pos_in_e, capacity)
    tok = sort_idx // k

    buf = jnp.zeros((e_local, capacity, d), x2d.dtype)
    buf = buf.at[b_e, b_c].set(x2d[tok], mode="drop")

    act = _ACTS[cfg.mlp_act]
    h = jnp.einsum("ecd,edf->ecf", buf, wi, preferred_element_type=jnp.float32)
    if wg is not None:
        g = jnp.einsum("ecd,edf->ecf", buf, wg,
                       preferred_element_type=jnp.float32)
        h = act(g) * h
    else:
        h = act(h)
    y_buf = jnp.einsum("ecf,efd->ecd", h.astype(x2d.dtype), wo,
                       preferred_element_type=jnp.float32).astype(x2d.dtype)

    y_assign = y_buf.at[b_e, b_c].get(mode="fill", fill_value=0)  # (T*k, D)
    wflat = weights.reshape(-1)[sort_idx].astype(y_assign.dtype)
    y = jnp.zeros((t, d), x2d.dtype).at[tok].add(y_assign * wflat[:, None])
    return y


def _sharded_body(x, weights, ids, wi, wg, wo, *, cfg: ModelConfig, ep: bool,
                  model_axis: str, gated: bool, capacity: int,
                  fsdp: bool = True):
    """shard_map body. x: (B_loc, S, D) replicated over model axis.

    fsdp=True (training): expert weights FSDP over "data", gathered on entry
    (backward becomes reduce-scatter).  fsdp=False (serving): weights stay
    resident 2-D sharded; the up-projection contracts a *sliced* d_model dim
    with a tiny psum over "data" — no per-step weight gathers at all.
    """
    wg = wg if gated else None
    if fsdp:
        wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        if wg is not None:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    if ep:
        e_local = wi.shape[0]
        e_offset = jax.lax.axis_index(model_axis) * e_local
    else:
        e_local, e_offset = cfg.n_experts, 0
    if fsdp:
        y = _dispatch_compute_combine(
            x2d, weights.reshape(b * s, -1), ids.reshape(b * s, -1),
            wi, wg, wo, cfg=cfg, e_offset=e_offset, e_local=e_local,
            capacity=capacity)
    else:
        y = _dispatch_contract_sharded(
            x2d, weights.reshape(b * s, -1), ids.reshape(b * s, -1),
            wi, wg, wo, cfg=cfg, e_offset=e_offset, e_local=e_local,
            capacity=capacity)
    y = jax.lax.psum(y, model_axis)
    return y.reshape(b, s, d)


def _dispatch_contract_sharded(x2d, weights, ids, wi, wg, wo, *,
                               cfg: ModelConfig, e_offset, e_local: int,
                               capacity: int):
    """Serving MoE: wi/wg hold a d_model slice (sharded over "data");
    up-projection partial sums are psum'd over "data" before the
    nonlinearity; wo is resident with full d_model output."""
    t, d = x2d.shape
    k = ids.shape[-1]
    d_loc = wi.shape[1]
    didx = jax.lax.axis_index("data")
    x_slice = jax.lax.dynamic_slice_in_dim(x2d, didx * d_loc, d_loc, 1)

    flat_ids = ids.reshape(-1)
    sort_idx = jnp.argsort(flat_ids, stable=True)
    s_ids = flat_ids[sort_idx]
    seg_start = jnp.searchsorted(s_ids, s_ids, side="left")
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - seg_start.astype(jnp.int32)
    local = (s_ids >= e_offset) & (s_ids < e_offset + e_local) \
        & (pos_in_e < capacity)
    b_e = jnp.where(local, s_ids - e_offset, e_local)
    b_c = jnp.where(local, pos_in_e, capacity)
    tok = sort_idx // k

    buf = jnp.zeros((e_local, capacity, d_loc), x2d.dtype)
    buf = buf.at[b_e, b_c].set(x_slice[tok], mode="drop")

    act = _ACTS[cfg.mlp_act]
    h = jnp.einsum("ecd,edf->ecf", buf, wi,
                   preferred_element_type=jnp.float32)
    h = jax.lax.psum(h, "data")                 # complete the d contraction
    if wg is not None:
        g = jnp.einsum("ecd,edf->ecf", buf, wg,
                       preferred_element_type=jnp.float32)
        g = jax.lax.psum(g, "data")
        h = act(g) * h
    else:
        h = act(h)
    y_buf = jnp.einsum("ecf,efd->ecd", h.astype(x2d.dtype), wo,
                       preferred_element_type=jnp.float32).astype(x2d.dtype)
    y_assign = y_buf.at[b_e, b_c].get(mode="fill", fill_value=0)
    wflat = weights.reshape(-1)[sort_idx].astype(y_assign.dtype)
    y = jnp.zeros((t, d), x2d.dtype).at[tok].add(y_assign * wflat[:, None])
    return y


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig,
              ctx: RunContext) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    weights, ids, aux = _route(x.reshape(b * s, d), params["router"], cfg)
    weights = weights.reshape(b, s, -1)
    ids = ids.reshape(b, s, -1)
    wg = params.get("wg")

    if ctx.mesh is None:
        cap = _capacity(b * s, cfg, ctx.moe_capacity_factor)
        y = _dispatch_compute_combine(
            x.reshape(b * s, d), weights.reshape(b * s, -1),
            ids.reshape(b * s, -1), params["wi"], wg, params["wo"], cfg=cfg,
            e_offset=0, e_local=cfg.n_experts, capacity=cap)
        return y.reshape(b, s, d), aux

    ep = cfg.n_experts % ctx.model_size == 0
    m = ctx.model_axis
    # Tokens replicate when the batch can't shard (e.g. long_500k batch=1).
    dp = ctx.dp_spec() if b % ctx.dp_size == 0 else None
    b_loc = b // ctx.dp_size if dp is not None else b
    # capacity is per-shard: local tokens routed into the global expert pool
    cap = _capacity(b_loc * s, cfg, ctx.moe_capacity_factor)
    fsdp = ctx.fsdp_weights
    if ep:
        # training: wi (E->m, D->data FSDP, F); serving: same 2-D sharding
        # but contraction-sharded compute (no gathers); wo output dim full
        w_specs = dict(wi=P(m, "data", None),
                       wo=P(m, None, "data" if fsdp else None))
    else:
        w_specs = dict(wi=P(None, "data", m),
                       wo=P(None, m, "data" if fsdp else None))
    in_specs = (P(dp, None, None), P(dp, None, None), P(dp, None, None),
                w_specs["wi"], w_specs["wi"], w_specs["wo"])
    body = functools.partial(_sharded_body, cfg=cfg, ep=ep, model_axis=m,
                             gated=cfg.mlp_gated, capacity=cap, fsdp=fsdp)
    y = jax.shard_map(
        body, mesh=ctx.mesh, in_specs=in_specs,
        out_specs=P(dp, None, None), check_vma=False,
    )(x, weights, ids, params["wi"],
      wg if wg is not None else params["wi"], params["wo"])
    return y, aux
