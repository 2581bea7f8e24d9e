"""One generator for every traffic mix (``traffic/<name>.json``).

The schedule (each request's lengths, its shared prefix and its arrival)
is drawn from the mix's own ``schedule_seed``; the run's seed draws the
token ids.  Every run of a mix so offers the same work at the same times,
and the seed changes what the requests say.  Lengths are spread evenly
over their range, arrival gaps are the quantiles of an exponential
distribution at the mix's rate, and the requests per shared prefix follow
the mix's Zipf shares, each list shuffled.  Where the mix names an
``open_with`` key of the configuration, every prompt opens with those
token ids, as the published tokenizer and chat template open each
conversation; a shared prefix opens with them too.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as the benchmark offers it."""

    rid: int
    prompt: np.ndarray       # int32 token ids, at most prompt_pad of them
    max_new_tokens: int
    arrival_t: float         # seconds after the window opens (inf: not yet)
    prefix: int = -1         # which shared prefix it opens with, -1 none


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per use of the seed (any size of int)."""
    return np.random.default_rng([stream, int(seed)])


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n integers spread evenly over [lo, hi]."""
    i = (np.arange(n) + 0.5) / max(n, 1)
    return (lo + np.floor(i * (hi - lo + 1))).astype(np.int64).clip(lo, hi)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """n items over k classes in Zipf(s) shares, largest remainder."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    out[np.argsort(-(exact - out))[: n - out.sum()]] += 1
    return out


def opening(mix: Dict, model: Dict) -> np.ndarray:
    """The ids every prompt of ``mix`` opens with under ``model``."""
    key = mix.get("open_with")
    return np.asarray(model[key] if key else [], np.int32)


def _text(rng: np.random.Generator, vocab: int, n: int,
          head: Sequence[int]) -> np.ndarray:
    """``n`` tokens: ``head``, then ids drawn from ``rng``."""
    body = rng.integers(0, vocab, n - len(head), dtype=np.int32)
    return np.concatenate([np.asarray(head, np.int32), body])


def request_count(mix: Dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return int(mix["pool"])


def generate(mix: Dict, model: Dict, seed: int, seconds: float,
             rate: Optional[float] = None) -> List[Spec]:
    """The requests of one run under configuration ``model``.  Open loop:
    arrivals over [0, seconds).  Closed loop: the first ``clients`` arrive
    at 0, the rest are released by the clients and carry ``inf`` until
    then."""
    vocab, head = model["vocab_size"], opening(mix, model)
    if rate is not None:
        mix = dict(mix, rate_per_s=rate)
    n = request_count(mix, seconds)
    order = rng_for(mix["schedule_seed"], 1)
    tokens = rng_for(seed, 2)
    plo, phi = mix["prompt_tokens"]
    olo, ohi = mix["output_tokens"]
    plen = order.permutation(spread(plo, phi, n))
    olen = order.permutation(spread(olo, ohi, n))
    shared = mix.get("shared_prefix")
    prefixes: List[np.ndarray] = []
    which = np.full(n, -1, np.int64)
    if shared:
        prefixes = [_text(tokens, vocab, shared["tokens"], head)
                    for _ in range(shared["count"])]
        counts = zipf_counts(n, shared["count"], shared["zipf_s"])
        which = order.permutation(np.repeat(np.arange(shared["count"]),
                                            counts))
    if mix["loop"] == "open":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = order.permutation(gaps) * (seconds / gaps.sum())
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        arrivals = np.full(n, math.inf)
        arrivals[: mix["clients"]] = 0.0
    out = []
    for i in range(n):
        if which[i] >= 0:
            own = tokens.integers(0, vocab, int(plen[i]), dtype=np.int32)
            prompt = np.concatenate([prefixes[which[i]], own])
        else:
            prompt = _text(tokens, vocab, int(plen[i]), head)
        if len(prompt) > mix["prompt_pad"]:
            raise ValueError(f"request {i}: prompt of {len(prompt)} tokens "
                             f"exceeds prompt_pad {mix['prompt_pad']}; the "
                             "server would cut it")
        out.append(Spec(i, prompt, int(olen[i]), float(arrivals[i]),
                        int(which[i])))
    return out


def check_sample(finished: List, tokens_of: Dict[int, int], seed: int,
                 mix: Dict, cached: Optional[Dict[int, int]] = None) -> List:
    """Requests whose outputs the reference checks: the one with the most
    served tokens, the first drawn from the seed of those admitted behind
    a cached prefix (``cached``: rid -> cached prompt tokens), so the
    suffix path is checked wherever the window took it, then others drawn
    from the seed until the sample holds ``check.tokens`` served tokens or
    ``check.max_requests`` requests."""
    if not finished:
        return []
    want, cap = mix["check"]["tokens"], mix["check"]["max_requests"]
    longest = max(finished, key=lambda s: (tokens_of[s.rid], -s.rid))
    rest = [s for s in finished if s is not longest]
    rest = [rest[i] for i in rng_for(seed, 3).permutation(len(rest))]
    out = [longest]
    cached = cached or {}
    if not cached.get(longest.rid, 0):
        hit = next((i for i, s in enumerate(rest) if cached.get(s.rid, 0)),
                   None)
        if hit is not None:
            out.append(rest.pop(hit))
    total = sum(tokens_of[s.rid] for s in out)
    for s in rest:
        if total >= want or len(out) >= cap:
            break
        out.append(s)
        total += tokens_of[s.rid]
    return out
