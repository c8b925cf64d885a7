"""The one traffic generator: every mix is a data file it reads.

Sizes and gaps are stratified so that every seed gives the same work in
another order: each block of ``block`` requests holds the same quantiles
of the mix's length distribution (and of the exponential inter-arrival
gap), and ``--seed`` only shuffles each block and draws the token ids.
A window that ends mid-block therefore sees nearly the same lengths and
arrivals on every seed.

A length distribution is ``{"median": m, "sigma": s, "min": a, "max": b}``:
lognormal with that median and log-space sigma, clipped to [a, b].
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

BLOCK = 16


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of ``seed`` (any non-negative int)."""
    return np.random.default_rng([seed, stream])


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lengths(dist: dict, n: int, rng: np.random.Generator,
            block: int = BLOCK) -> np.ndarray:
    """``n`` lengths: per block, the same clipped lognormal quantiles."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(block)])
    base = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    base = np.clip(np.rint(base), dist["min"], dist["max"]).astype(np.int64)
    blocks = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(blocks)[:n]


def arrivals(rate: float, n: int, rng: np.random.Generator,
             block: int = BLOCK) -> np.ndarray:
    """Due times (s) of ``n`` Poisson arrivals at ``rate`` per second:
    per block, the same exponential gap quantiles in a shuffled order."""
    base = -np.log1p(-_quantiles(block)) / rate
    gaps = np.concatenate([rng.permutation(base)
                           for _ in range(-(-n // block))])[:n]
    return np.cumsum(gaps)


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list:
    return rng.integers(1, vocab, n).tolist()


def requests(mix: dict, n: int, seed: int, vocab: int) -> list:
    """``(prefix, [(prompt, max_new)] * n)`` for a serving mix.  A mix
    with ``shared_prefix_tokens`` puts one prefix, drawn from the seed,
    in front of every prompt; otherwise the prefix is empty."""
    rng = rng_for(seed, 1)
    suffix = lengths(mix["prompt_tokens"], n, rng)
    new = lengths(mix["new_tokens"], n, rng)
    prefix = token_ids(rng, mix.get("shared_prefix_tokens", 0), vocab)
    return prefix, [(prefix + token_ids(rng, int(s), vocab), int(m))
                    for s, m in zip(suffix, new)]
