"""Plain float32 reference of a dense decoder, read from the configuration
file, for the outputs check.  It imports nothing of the serving program.

Straight ``jax.numpy`` at float32 and ``highest`` matmul precision, one
layer at a time so that a 3.8B model fits beside its bf16 weights:
RMSNorm with a ``1 + scale`` gain, rotate-half RoPE, grouped-query causal
attention (within the published sliding window, where there is one), a
SiLU-gated MLP, tied or separate output embeddings.

``quant="int8"`` is the control: the same pass with every weight matrix
(the layers', the embedding table's and the output head's) rounded to int8
per output channel, the precision below the configuration's bf16 that a
faster serving path would be tempted to use.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# attention scores of one row block stay under this many bytes
SCORE_BYTES = 1 << 30


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


def served_sequences(prompts, outputs, prompt_pad: int, rows: int,
                     max_new: int):
    """Rows of (prompt padded with token 0 to ``prompt_pad``, then every
    served token but the last) and the positions whose logits chose each
    served token, padded to ``rows`` rows of ``max_new`` positions so that
    every run of a mix checks one shape."""
    seqs = np.zeros((rows, prompt_pad + max_new - 1), np.int32)
    at = np.full((rows, max_new), prompt_pad - 1, np.int32)
    for r, (p, o) in enumerate(zip(prompts, outputs)):
        seqs[r, :len(p)] = p
        seqs[r, prompt_pad:prompt_pad + len(o) - 1] = o[:-1]
        at[r] = prompt_pad - 1 + np.minimum(np.arange(max_new), len(o) - 1)
    return seqs, at


@jax.jit
def _gaps(ref, chosen):
    pick = jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    return (ref.max(-1) - pick) / ref.std(-1)


def gaps(ref_blocks: List, chosen: np.ndarray, valid: np.ndarray,
         ) -> np.ndarray:
    """How far below the reference's best each chosen token's reference
    logit lies, in standard deviations of that position's reference logits
    (computed on the device, block by block); ``valid`` masks padding
    positions (their gap reads 0).  ``chosen`` may be a list of device
    blocks (a control's own first choices) or one host array."""
    out, r = [], 0
    for i, ref in enumerate(ref_blocks):
        c = (chosen[i] if isinstance(chosen, list)
             else jnp.asarray(chosen[r:r + ref.shape[0]]))
        out.append(np.asarray(_gaps(ref, c), np.float64))
        r += ref.shape[0]
    return np.where(valid, np.concatenate(out, 0), 0.0)


def first_choices(blocks: List) -> List:
    """Each position's highest-scoring token, per block, on the device."""
    return [jnp.argmax(b, -1) for b in blocks]
