"""What the outputs check shares across model families: the served rows
and positions it checks, and the gaps of chosen tokens below the
reference's best.  Each family's float32 reference and its control are
``Reference`` in ``families/<architecture>.py`` (``spec.family``).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np


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
