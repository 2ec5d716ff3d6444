"""Seeded training batches, made by the benchmark on the host.

A batch is `batch` rows of `seq` + 1 tokens of one concatenated document
stream: token ranks are Zipf-distributed (p ~ 1 / (rank + shift) ** alpha)
and a document ends with token 0 after a geometric number of tokens, as
Megatron / PaddleFleetX GPT pretraining packs its corpus by default (full
causal attention across boundaries). ids = row[:-1], labels = row[1:].
Batch k of seed s is a function of (s, k) alone, so the reference can make
the first batches again; every row differs.
"""
from __future__ import annotations

import numpy as np


class TokenStream:
    def __init__(self, job: dict, vocab: int, seed: int):
        self.batch, self.seq = int(job["batch"]), int(job["seq"])
        self.seed = int(seed)
        self.mean_doc = float(job["mean_document_tokens"])
        ranks = np.arange(1, vocab, dtype=np.float64)   # token 0 = EOS
        p = 1.0 / (ranks + float(job["zipf_shift"])) ** float(
            job["zipf_alpha"])
        self._cdf = np.cumsum(p / p.sum())

    def batch_at(self, k: int):
        """(ids, labels), int64 [batch, seq] each."""
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     self.seed >> 32, k])
        n = self.batch * (self.seq + 1)
        tok = 1 + np.searchsorted(self._cdf, rng.random(n), side="right")
        tok = np.minimum(tok, len(self._cdf)).astype(np.int64)
        tok[rng.random(n) < 1.0 / self.mean_doc] = 0
        rows = tok.reshape(self.batch, self.seq + 1)
        return (np.ascontiguousarray(rows[:, :-1]),
                np.ascontiguousarray(rows[:, 1:]))

    def __iter__(self):
        k = 0
        while True:
            yield self.batch_at(k)
            k += 1
