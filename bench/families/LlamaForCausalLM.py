"""The dense Llama-style decoder family: what the benchmark needs to know of
a configuration file whose ``architectures`` names ``LlamaForCausalLM``.

A family module provides ``program_fields``, ``shapes``, ``Reference``
and the four work counts; ``harness.spec.family`` finds it by the
architecture's name, so a new family joins through an added file.

- ``program_fields``: the program-config attributes the file implies,
  which ``spec.program_config`` compares with the registered config.
- ``shapes``: the parameter tree ``weights.make_params`` draws.
- ``Reference``: the plain float32 reference of the outputs check, and its
  int8 control.  It imports nothing of the serving program.
- ``matmul_flops_per_token``, ``kv_bytes_per_token``, ``attention_flops``,
  ``attention_bytes``: the work each kind of step needs, the yardstick of
  every roofline share and of ``mfu`` (through ``harness.flops``).  Work is
  counted as the algorithm needs it (live tokens, causal attention), never
  as a kernel's grid pads it.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

BF16 = 2
# attention scores of one row block stay under this many bytes
SCORE_BYTES = 1 << 30


# ------------------------------------------------------------ program check
def program_fields(model: Dict, capacity: Optional[int] = None) -> Dict:
    """The program-config attributes (name -> value) that ``model`` implies;
    raises where the file describes something the program does not
    implement at ``capacity``."""
    window = model.get("sliding_window")
    if window is not None and capacity is not None and capacity > window:
        raise ValueError(f"capacity {capacity} exceeds the published "
                         f"sliding window {window}, which the program does "
                         "not implement")
    return {
        "d_model": model["hidden_size"], "d_ff": model["intermediate_size"],
        "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"], "n_layers": model["num_hidden_layers"],
        "vocab_size": model["vocab_size"],
        "rope_theta": model["rope_theta"],
        "tie_embeddings": model["tie_word_embeddings"],
        "mlp_act": model["hidden_act"], "mlp_gated": True,
        "norm_type": "rmsnorm", "qkv_bias": False, "window": None,
        "family": "dense", "causal": True, "dtype": model["torch_dtype"],
        "logit_softcap": None, "final_softcap": None,
        "scale_embeddings": False, "block_pattern": ("attn",),
    }


# -------------------------------------------------------------- weight tree
def shapes(m: Dict) -> Dict:
    """(shape, fan_in) of every leaf; fan_in None marks a norm gain."""
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    h, g, n = (m["num_attention_heads"], m["num_key_value_heads"],
               m["head_dim"])
    L = m["num_hidden_layers"]
    tree = {
        "embed": ((v, d), d),
        "final_norm": {"scale": ((d,), None)},
        "layers": {
            "stack": {"b0": {
                "attn": {"wq": ((L, d, h, n), d), "wk": ((L, d, g, n), d),
                         "wv": ((L, d, g, n), d), "wo": ((L, h, n, d), h * n)},
                "ffn": {"wi": ((L, d, f), d), "wg": ((L, d, f), d),
                        "wo": ((L, f, d), f)},
                "norm1": {"scale": ((L, d), None)},
                "norm2": {"scale": ((L, d), None)},
            }},
            "rest": {},
        },
    }
    if not m["tie_word_embeddings"]:
        tree["head"] = ((d, v), d)
    return tree


# ---------------------------------------------------------------- reference
def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale)


def _int8(w, in_axes):
    """Round to int8 per output channel (absmax over the input axes)."""
    s = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


# input axes of each layer matrix, for the control's per-channel rounding
_IN_AXES = {("attn", "wq"): (0,), ("attn", "wk"): (0,), ("attn", "wv"): (0,),
            ("attn", "wo"): (0, 1), ("ffn", "wi"): (0,), ("ffn", "wg"): (0,),
            ("ffn", "wo"): (0,)}


class Reference:
    """Plain float32 reference of a dense decoder, read from the
    configuration file.

    Straight ``jax.numpy`` at float32 and ``highest`` matmul precision, one
    layer at a time so that a 3.8B model fits beside its bf16 weights:
    RMSNorm with a ``1 + scale`` gain, rotate-half RoPE, grouped-query
    causal attention (within the published sliding window, where there is
    one), a SiLU-gated MLP, tied or separate output embeddings.

    ``quant="int8"`` is the control: the same pass with every weight matrix
    (the layers', the embedding table's and the output head's) rounded to
    int8 per output channel, the precision below the configuration's bf16
    that a faster serving path would be tempted to use.
    """

    def __init__(self, m: Dict, quant: Optional[str] = None):
        if quant not in (None, "int8"):
            raise ValueError(f"unknown control precision {quant!r}")
        self.m = m
        self.quant = quant
        h, g = m["num_attention_heads"], m["num_key_value_heads"]
        self.n = m["head_dim"]
        self.group = h // g
        self.eps = m["rms_norm_eps"]
        self.window = m.get("sliding_window")
        half = self.n // 2
        self.inv_freq = m["rope_theta"] ** (-np.arange(half) / half)
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)
        self._embed = jax.jit(self._embed_fn)

    def _layer_fn(self, x, lp):
        """x (B, S, D) float32 -> x after one block."""
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        if self.quant == "int8":
            lp = {blk: {k: (_int8(w, _IN_AXES[(blk, k)])
                            if (blk, k) in _IN_AXES else w)
                        for k, w in ws.items()}
                  for blk, ws in lp.items()}
        s, n, half = x.shape[1], self.n, self.n // 2
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * self.inv_freq
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]

        def rope(t):                                   # (B, S, H, n)
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                                   -1)

        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        keep = j <= i
        if self.window is not None:
            keep = keep & (i - j < self.window)
        a = lp["attn"]
        hh = _rms_norm(x, lp["norm1"]["scale"], self.eps)
        q = rope(jnp.einsum("bsd,dhn->bshn", hh, a["wq"]))
        k = rope(jnp.einsum("bsd,dhn->bshn", hh, a["wk"]))
        v = jnp.einsum("bsd,dhn->bshn", hh, a["wv"])
        k = jnp.repeat(k, self.group, axis=2)
        v = jnp.repeat(v, self.group, axis=2)
        sc = jnp.einsum("bqhn,bkhn->bhqk", q, k) / np.sqrt(n)
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhn->bqhn", pr, v)
        x = x + jnp.einsum("bqhn,hnd->bqd", o, a["wo"])
        f = lp["ffn"]
        hh = _rms_norm(x, lp["norm2"]["scale"], self.eps)
        u = jax.nn.silu(hh @ f["wg"]) * (hh @ f["wi"])
        return x + u @ f["wo"]

    def _head_fn(self, x, final_scale, head):
        """x (B, K, D) at the checked positions -> logits (B, K, V)."""
        x = _rms_norm(x, final_scale.astype(jnp.float32), self.eps)
        head = head.astype(jnp.float32)
        if self.quant == "int8":
            head = _int8(head, (0,))
        return x @ head

    def _embed_fn(self, table, seqs):
        table = table.astype(jnp.float32)
        if self.quant == "int8":
            table = _int8(table, (1,))
        return jnp.take(table, seqs, axis=0)

    def logits(self, params, seqs: np.ndarray, at: np.ndarray) -> List:
        """Logits (device arrays, one per row block) of each row of ``seqs``
        (B, S) at positions ``at`` (B, K)."""
        with jax.default_matmul_precision("highest"):
            return [self._block(params, seqs[r:r + rb], at[r:r + rb])
                    for r, rb in self._row_blocks(seqs.shape)]

    def _row_blocks(self, shape):
        b, s = shape
        per_row = self.m["num_attention_heads"] * s * s * 4
        rb = max(1, min(b, SCORE_BYTES // per_row))
        return [(r, rb) for r in range(0, b, rb)]

    def _block(self, params, seqs, at):
        stack = params["layers"]["stack"]["b0"]
        embed = params["embed"]
        x = self._embed(embed, jnp.asarray(seqs))
        for li in range(self.m["num_hidden_layers"]):
            x = self._layer(x, jax.tree.map(lambda a: a[li], stack))
        x = jnp.take_along_axis(x, jnp.asarray(at)[..., None], axis=1)
        head = embed.T if self.m["tie_word_embeddings"] else params["head"]
        return self._head(x, params["final_norm"]["scale"], head)


# -------------------------------------------------------------- work counts
def matmul_flops_per_token(m: Dict) -> float:
    """Forward matmul FLOPs of one token: every layer matrix and the
    output head (the embedding lookup is no matmul)."""
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    h, g, n = (m["num_attention_heads"], m["num_key_value_heads"],
               m["head_dim"])
    per_layer = d * h * n + 2 * d * g * n + h * n * d + 3 * d * f
    return 2.0 * (m["num_hidden_layers"] * per_layer + d * v)


def kv_bytes_per_token(m: Dict) -> float:
    """Bytes of keys and values one context token holds over all layers."""
    return (2.0 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * BF16)


def attention_flops(m: Dict, cached: int, new: int) -> float:
    """QK^T and PV FLOPs of ``new`` causal query tokens that follow
    ``cached`` context tokens (each attends to itself and all before)."""
    ctx = new * cached + new * (new + 1) / 2.0
    return (4.0 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * ctx)


def attention_bytes(m: Dict, cached: int, new: int) -> float:
    """Least bytes attention moves for ``new`` query tokens after ``cached``
    context: each query and output once, each key and value once."""
    L, h, g, n = (m["num_hidden_layers"], m["num_attention_heads"],
                  m["num_key_value_heads"], m["head_dim"])
    return L * n * BF16 * (2.0 * h * new + 2.0 * g * (cached + new))
