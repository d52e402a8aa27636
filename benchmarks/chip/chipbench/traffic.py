"""The one generator of traffic, driven by a mix file.

A ``serve`` mix gives an open-loop arrival process and length
distributions.  Every seed gets the same set of prompt lengths, output
lengths and gaps between arrivals, drawn at stratified quantiles of the
mix's distributions; the seed only orders them and picks the token ids.
So the work of a window is the same for every seed.

Warm-up requests come before the window and are served, but are not
counted; window requests are due at ``warmup_s + t`` for ``t`` in
``[0, seconds)``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

MASK32 = 0xFFFFFFFF


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed; takes
    any non-negative seed, 64 bits included."""
    return np.random.default_rng([seed & MASK32, (seed >> 32) & MASK32,
                                  stream])


def jax_key(seed: int, stream: int):
    """A JAX key from all bits of ``seed`` (``PRNGKey`` keeps 32)."""
    import jax

    k = jax.random.PRNGKey(seed & MASK32)
    k = jax.random.fold_in(k, (seed >> 32) & MASK32)
    return jax.random.fold_in(k, stream)


def small_seed(seed: int) -> int:
    """A 31-bit seed for code that takes an int32 seed."""
    x = (seed * 0x9E3779B97F4A7C15 + 0x632BE59BD9B4E019) & (2 ** 64 - 1)
    return (x ^ (x >> 29)) & 0x7FFFFFFF


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> list:
    nd = NormalDist()
    return [int(min(hi, max(lo, round(median * math.exp(
        sigma * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def gaps(n: int, span: float) -> np.ndarray:
    """``n`` exponential gaps at stratified quantiles, scaled to sum to
    ``span``: a Poisson process of rate n / span with a fixed set of
    gaps."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (span / g.sum())


@dataclass
class Arrival:
    rid: int
    due: float          # seconds after the start of the warm-up
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int
    in_window: bool


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Arrivals of a warm-up of ``mix["warmup_s"]`` and a window of
    ``seconds`` at ``mix["rate_per_s"]``."""
    rate = float(mix["rate_per_s"])
    warm = float(mix["warmup_s"])
    p, o = mix["prompt_len"], mix["output_len"]
    out = []
    rid = 0
    for part, (t0, span) in enumerate([(0.0, warm), (warm, seconds)]):
        n = max(1, int(round(rate * span)))
        r = rng(seed, 1 + part)
        plen = r.permutation(lognormal_quantiles(
            n, p["median"], p["sigma"], p["min"], p["max"]))
        olen = r.permutation(lognormal_quantiles(
            n, o["median"], o["sigma"], o["min"], o["max"]))
        g = r.permutation(gaps(n, span))
        # the first arrival of a part is due at its start; the last gap
        # leads past its end
        due = t0 + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for i in range(n):
            prompt = r.integers(0, vocab, int(plen[i]), dtype=np.int32)
            out.append(Arrival(rid, float(due[i]), prompt, int(olen[i]),
                               part == 1))
            rid += 1
    return out


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order statistics."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))
