"""Open loop: requests arrive on a schedule, whether or not earlier ones
have finished, so the queue can grow.

Mix keys: ``rate_per_s`` (mean arrivals per second; the gaps are an
exponential pool, so arrivals are Poisson up to the stratified draw),
``prompt`` and ``output`` (length distributions, see ``common.quantiles``).
"""
from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np

from chipbench.gen import common


class OpenLoop:
    kind = "open_loop"

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        n = common.pool_size(mix)
        gap = {"dist": "exponential", "mean": 1.0 / mix["rate_per_s"]}
        self._gaps = common.Pool(common.quantiles(gap, n), seed, 1)
        self._prompt = common.Pool(common.quantiles(mix["prompt"], n),
                                   seed, 2)
        self._output = common.Pool(common.quantiles(mix["output"], n),
                                   seed, 3)

    def request(self, i: int) -> Tuple[np.ndarray, int]:
        """Prompt tokens and output budget of request ``i``."""
        n = self._prompt[i]
        return common.tokens(self.seed, i, n, self.vocab), self._output[i]

    def arrivals(self) -> Iterator[Tuple[float, int]]:
        """``(seconds after the window opens, request index)``, forever;
        the first request is due at the opening."""
        t, i = 0.0, 0
        while True:
            yield t, i
            t += self._gaps[i]
            i += 1


Generator = OpenLoop
