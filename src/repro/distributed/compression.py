"""Gradient compression with error feedback (fleet distributed-opt trick).

int8 quantization with per-tensor scale + error-feedback residuals: the
cross-replica gradient reduction moves 1 byte/param instead of 4 (or 2),
cutting the pod-axis collective roofline term ~4x, while error feedback
keeps convergence (residual carried into the next step).

Used by the manual-collective (shard_map) DP trainer in
``repro.launch.train``; the GSPMD path keeps XLA's native all-reduce.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_int8(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def kv_quant_axes(ndim: int) -> Tuple[int, ...]:
    """Reduction axes for per-(block, head) KV scales.

    Gathered KV blocks are laid out ``(n_blocks, ..., n_kv_heads,
    block_size, head_dim)`` (the head-major pool layout with the block
    axis moved to 0); the scale must survive per block AND per head, so
    reduce every axis except 0 and the head axis at -3.  Leaves too small
    to carry a head axis (ndim < 4) fall back to per-block scales.
    """
    if ndim >= 4:
        return tuple(i for i in range(1, ndim) if i != ndim - 3)
    return tuple(range(1, ndim))


def quantize_kv_blocks(blocks: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(block, head) int8 quantization of gathered KV blocks.

    ``blocks``: (n, ...) gathered along the block axis (head axis at -3
    when present).  Returns ``(q int8, scales float32)`` with ``scales``
    keepdims-shaped so it broadcasts against ``blocks`` — the compressed
    KV transfer ships 1 byte/element plus one float32 scale per
    (block, head) instead of the full-width payload (ADR-009).
    """
    v = blocks.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v), axis=kv_quant_axes(blocks.ndim),
                   keepdims=True)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv_blocks(q: jax.Array, scale: jax.Array,
                         dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_kv_blocks` back to the pool dtype."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum(g: jax.Array, residual: jax.Array, axis_name: str
                    ) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback int8 all-reduce over ``axis_name`` (inside shard_map).

    Wire bytes: 1 per element (int8 all-gather) + 4 per shard (scales),
    vs 4 per element for the fp32 psum it replaces.
    """
    v = g.astype(jnp.float32) + residual
    q, scale = quantize_int8(v)
    new_residual = v - dequantize_int8(q, scale)
    # semantics of an int8 ring all-reduce: gather peers' int8 shards +
    # their scales, sum dequantized
    qs = jax.lax.all_gather(q, axis_name)            # (k, ...)
    scales = jax.lax.all_gather(scale, axis_name)    # (k,)
    summed = jnp.tensordot(scales,
                           qs.astype(jnp.float32), axes=((0,), (0,)))
    k = qs.shape[0]
    return summed / k, new_residual


def tree_compressed_pmean(grads, residuals, axis_name: str):
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    out = [compressed_psum(g, r, axis_name) for g, r in zip(flat_g, flat_r)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))
