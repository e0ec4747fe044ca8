"""The token stream a traffic file describes, made from the run's seed.

The global batch of step ``t`` is a pure function of ``(seed, t)`` and does
not depend on how many replicas share it, so a rescaled job and the
reference see the same rows.  Tokens follow a Zipf law over the vocabulary;
every odd position after the first is a fixed function of the token before
it, so the loss can fall.  The generator and its arithmetic are copied from
the program's ``data/pipeline.py`` so that a change there cannot move the
benchmark's inputs.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

_MIX = 2654435761


class TokenStream:
    """Duck-types the program's stream: ``global_batch_at``, ``shard_bounds``."""

    def __init__(self, seed: int, vocab_size: int, global_batch: int,
                 seq_len: int, zipf_exponent: float = 1.0, span=None):
        self.seed, self.vocab_size = seed, vocab_size
        self.global_batch, self.seq_len = global_batch, seq_len
        p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -zipf_exponent
        self._cdf = np.cumsum(p / p.sum())
        self._span = span

    def _make(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        B, S, V = self.global_batch, self.seq_len, self.vocab_size
        u = rng.random((B, S + 1))
        base = np.minimum(np.searchsorted(self._cdf, u), V - 1)
        nxt = base * _MIX % V
        base[:, 1::2] = nxt[:, 0:-1:2]
        return {"tokens": np.ascontiguousarray(base[:, :-1], np.int32),
                "labels": np.ascontiguousarray(base[:, 1:], np.int32)}

    def global_batch_at(self, step: int) -> Dict[str, np.ndarray]:
        if self._span is None:
            return self._make(step)
        with self._span("bench.batch"):
            return self._make(step)

    def shard_bounds(self, replica_idx: int, num_replicas: int
                     ) -> Tuple[int, int]:
        if self.global_batch % num_replicas:
            raise ValueError(f"global_batch {self.global_batch} not divisible "
                             f"by {num_replicas}")
        per = self.global_batch // num_replicas
        return replica_idx * per, (replica_idx + 1) * per


def stream_for(traffic: dict, vocab_size: int, seed: int, span=None
               ) -> TokenStream:
    return TokenStream(seed, vocab_size, traffic["global_batch"],
                       traffic["seq_len"],
                       traffic.get("zipf_exponent", 1.0), span)
