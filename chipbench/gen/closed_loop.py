"""Closed loop: ``clients`` callers, each sending its next request the
moment its last one completes, so a slow system receives less load.

Mix keys: ``clients``, ``prompt`` and ``output`` (length distributions, see
``common.quantiles``).  Request ``i`` belongs to client ``i % clients``.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from chipbench.gen import common


class ClosedLoop:
    kind = "closed_loop"

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        n = common.pool_size(mix)
        self.clients = int(mix["clients"])
        self._prompt = common.Pool(common.quantiles(mix["prompt"], n),
                                   seed, 2)
        self._output = common.Pool(common.quantiles(mix["output"], n),
                                   seed, 3)

    def request(self, i: int) -> Tuple[np.ndarray, int]:
        """Prompt tokens and output budget of request ``i``."""
        n = self._prompt[i]
        return common.tokens(self.seed, i, n, self.vocab), self._output[i]


Generator = ClosedLoop
