"""Logical-axis -> mesh-axis sharding rules (DESIGN.md §4).

Divisibility-aware: a logical dim whose size does not divide its preferred
mesh axis falls back to replication (e.g. Mixtral's 8 experts on a 16-wide
model axis -> expert dim replicated, d_ff takes the model axis instead via
the "mlp" rule).  A mesh axis is used at most once per tensor.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# logical axis -> preferred mesh axis / tuple of axes (None = replicated)
DEFAULT_RULES: Dict[str, object] = {
    # data-parallel dims
    "batch": ("pod", "data"),
    # tensor-parallel dims
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "lru": "model",
    "rank": "model",
    # KV-cache sequence dim: takes the model axis when kv_heads can't
    # (flash-decoding style partial-softmax sharding; see §Perf H2)
    "kv_seq": "model",
    # FSDP dim
    "embed": "data",
    "lru_in": "data",
    # replicated
    "head_dim": None,
    "conv": None,
    "layers": None,
    "embed_out": None,
    "experts_r": None,
}

# allocation priority: earlier entries claim mesh axes first (a tensor's
# dims are assigned in this order, then the spec is emitted in dim order)
_PRIORITY = ["batch", "vocab", "heads", "kv_heads", "mlp", "experts", "lru",
             "rank", "kv_seq", "embed", "lru_in"]

# ZeRO-SP profile (§Perf H3): weights FSDP-only (gathered per layer), the
# model axis carries the sequence — cuts Megatron activation all-reduces
ZERO_SP_RULES: Dict[str, object] = dict(
    DEFAULT_RULES,
    heads=None, kv_heads=None, mlp=None, lru=None, rank=None,
)


# serve profile (§Perf H2b): params resident (model-axis TP dims only, no
# FSDP dim) — eliminates per-step weight gathers on the decode path
SERVE_RULES: Dict[str, object] = dict(
    DEFAULT_RULES, embed=None, lru_in=None,
)


# pre-hillclimb baseline: no kv-cache sequence sharding (EXPERIMENTS §Perf)
LEGACY_RULES: Dict[str, object] = dict(DEFAULT_RULES, kv_seq=None)


def rules_for(profile: str) -> Dict[str, object]:
    if profile == "zero-sp":
        return ZERO_SP_RULES
    if profile == "serve":
        return SERVE_RULES
    if profile == "legacy":
        return LEGACY_RULES
    return DEFAULT_RULES

# batch dims shard over the pure-DP axes (pod + data)
def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]], mesh: Mesh,
             rules: Dict[str, Optional[str]] = None) -> P:
    """PartitionSpec for one tensor from its logical axes."""
    rules = rules or DEFAULT_RULES
    used = set()
    out = [None] * len(axes)
    order = sorted(range(len(axes)),
                   key=lambda i: _PRIORITY.index(axes[i])
                   if axes[i] in _PRIORITY else len(_PRIORITY))
    for i in order:
        size, logical = shape[i], axes[i]
        pref = rules.get(logical) if logical is not None else None
        if pref is None:
            continue
        cand = tuple(a for a in (pref if isinstance(pref, tuple) else (pref,))
                     if a in mesh.shape and a not in used)
        total = 1
        for a in cand:
            total *= mesh.shape[a]
        if not cand or size % total != 0:
            continue
        out[i] = cand if len(cand) > 1 else cand[0]
        used.update(cand)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_specs(abstract_tree, axes_tree, mesh: Mesh, rules=None):
    """PartitionSpec pytree matching an abstract (ShapeDtypeStruct) tree."""
    return jax.tree.map(
        lambda leaf, axes: spec_for(leaf.shape, axes, mesh, rules),
        abstract_tree, axes_tree)


def tree_shardings(abstract_tree, axes_tree, mesh: Mesh, rules=None):
    specs = tree_specs(abstract_tree, axes_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(ndim: int, mesh: Mesh) -> P:
    """Inputs: leading batch dim over (pod, data)."""
    dp = batch_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    return P(dp, *([None] * (ndim - 1)))


def input_shardings(batch_tree, mesh: Mesh, global_batch: int):
    """Shardings for an input batch pytree; replicates non-divisible batches
    (long_500k batch=1)."""
    dp = batch_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def f(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                and leaf.shape[0] == global_batch \
                and global_batch % dp_size == 0:
            return NamedSharding(mesh, batch_spec(leaf.ndim, mesh))
        return NamedSharding(mesh, P())

    return jax.tree.map(f, batch_tree)
