"""The one traffic generator: every mix is a data file it reads.

Sizes and gaps are stratified so that every seed gives the same work in
another order: the ``block`` quantiles of the mix's length distribution
(and of the exponential inter-arrival gap) come in an order that the
seed draws, and that order repeats, from a point the seed draws.  Any
``block`` consecutive requests therefore hold each quantile once, and a
window of any length sees nearly the same lengths and arrivals on every
seed; a fresh shuffle of every block would let a window that ends
mid-block hold one long prompt fewer on some seeds, and move a tail such
as a 90th-percentile time to first token by a whole quantile.  ``--seed``
also draws the token ids.

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


def _cycle(base: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values: ``base`` in an order drawn from ``rng``, repeated,
    starting at a drawn point of that order."""
    order = rng.permutation(base)
    start = int(rng.integers(len(base)))
    return np.resize(np.roll(order, -start), n)


def lengths(dist: dict, n: int, rng: np.random.Generator,
            block: int = BLOCK) -> np.ndarray:
    """``n`` lengths: the ``block`` clipped lognormal quantiles, cycled."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(block)])
    base = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    base = np.clip(np.rint(base), dist["min"], dist["max"]).astype(np.int64)
    return _cycle(base, n, rng)


def arrivals(rate: float, n: int, rng: np.random.Generator,
             block: int = BLOCK) -> np.ndarray:
    """Due times (s) of ``n`` arrivals at a mean ``rate`` per second: the
    ``block`` quantiles of the exponential gap, cycled, so not a Poisson
    stream: no stretch holds more arrivals than the one order does."""
    return np.cumsum(_cycle(-np.log1p(-_quantiles(block)) / rate, n, rng))


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
