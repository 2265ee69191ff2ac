"""What both generators share: length pools and request token streams.

Every seed gets the same multiset of lengths (and of arrival gaps), only in
another order, so that a seed changes which request comes when and never
how much work a run holds.  A pool is ``pool`` (the mix's key, else
``POOL``) evenly spaced quantiles of the mix's distribution; the stream
walks the pool in blocks, each block a fresh permutation drawn from the
seed.  A pool about as large as the requests of one window gives every
window nearly the same multiset.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

POOL = 64


def pool_size(mix: dict) -> int:
    return int(mix.get("pool", POOL))


def quantiles(spec: dict, n: int = POOL) -> np.ndarray:
    """``n`` evenly spaced quantiles of ``spec``, sorted ascending.

    ``spec`` is ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
    ``{"dist": "uniform", "min", "max"}`` (integers, both ends included) or
    ``{"dist": "exponential", "mean"}`` (floats, for arrival gaps)."""
    qs = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(q) for q in qs])
        x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
        return np.clip(x, spec["min"], spec["max"]).astype(np.int64)
    if kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        return np.floor(lo + qs * (hi - lo + 1)).astype(np.int64)
    if kind == "exponential":
        return np.array([-math.log1p(-q) * spec["mean"] for q in qs])
    raise ValueError(f"unknown distribution {kind!r}")


class Pool:
    """Values of a fixed multiset, in blocks permuted by the seed."""

    def __init__(self, values: np.ndarray, seed: int, stream: int):
        self.values = np.asarray(values)
        self.seed = seed
        self.stream = stream
        self._blocks: dict = {}

    def __getitem__(self, i: int):
        b, j = divmod(i, len(self.values))
        if b not in self._blocks:
            rng = np.random.default_rng([self.seed, self.stream, b])
            self._blocks[b] = rng.permutation(self.values)
        return self._blocks[b][j].item()


def tokens(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """The prompt of request ``index``: ``n`` token ids from the seed."""
    rng = np.random.default_rng([seed, 7, index])
    return rng.integers(0, vocab, size=n).astype(np.int32)


def check_lengths(mix: dict, max_len: int) -> None:
    """Refuse a mix whose longest request cannot fit one slot."""
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    if longest >= max_len:
        raise ValueError(f"the mix's longest request ({longest} tokens) "
                         f"does not fit max_len {max_len}")
