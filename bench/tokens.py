"""The training cells' token source: ids drawn uniformly from the
vocabulary, a pure function of (seed, step), so any batch can be made alone
and no two rows repeat. The dense SSM step costs the same whatever the ids,
so no document structure is modelled. The object has the interface of the
program's ``SyntheticLMData`` (``batch_at(step)``), and is handed to
``DataPipelineStateObject`` in its place.
"""
from __future__ import annotations

import numpy as np


class UniformTokens:
    def __init__(self, vocab_size: int, global_batch: int, seq_len: int, seed: int) -> None:
        self.vocab_size = vocab_size
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.seed = seed

    def batch_at(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed % 2**64, step]))
        return rng.integers(0, self.vocab_size, (self.global_batch, self.seq_len + 1), np.int32)
