"""Training batches from the seed: the benchmark's own copy of the repo's
hidden-Markov Zipf token stream (`repro.data.SyntheticLM`), so that no later
change to the program moves the yardstick.

Batch i is a pure function of (seed, i): the same seed gives the same
batches, and every step's rows differ. Encoder traffic is MLM-masked: a
share `mlm_rate` of positions is replaced by the last vocabulary id and only
those positions carry labels. Decoder traffic predicts the next token.
Shapes do not depend on the seed, so every seed does the same work.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class TokenStream:
    """State-dependent unigram mixtures over a Zipf base, fixed per seed.

    `batch(i)` is what the training loop calls each step; the harness wraps
    it in a profiler span named `bench.batch` so the host time it takes is
    readable from the trace."""

    def __init__(self, *, vocab_size: int, seq_len: int, global_batch: int,
                 seed: int, encoder: bool, n_states: int = 64,
                 zipf_exponent: float = 1.1, boost_share: float = 0.3,
                 mlm_rate: float = 0.15, segment: int = 64):
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.seed = seed
        self.encoder = encoder
        self.n_states = n_states
        self.boost_share = boost_share
        self.mlm_rate = mlm_rate
        self.segment = segment
        rng = np.random.default_rng(seed)
        zipf = 1.0 / np.arange(1, vocab_size + 1) ** zipf_exponent
        self.base = zipf / zipf.sum()
        self.boost_idx = rng.integers(0, vocab_size, size=(n_states, 32))

    def batch(self, index: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, index))
        b, s, seg = self.global_batch, self.seq_len, self.segment
        nseg = -(-s // seg)
        states = rng.integers(0, self.n_states, size=(b, nseg))
        base_draw = rng.choice(self.vocab_size, p=self.base,
                               size=(b, nseg, seg))
        boost_col = rng.integers(0, 32, size=(b, nseg, seg))
        boosted = self.boost_idx[states[..., None], boost_col]
        use_boost = rng.random((b, nseg, seg)) < self.boost_share
        toks = np.where(use_boost, boosted, base_draw).reshape(b, nseg * seg)
        toks = toks[:, :s].astype(np.int32)
        if self.encoder:
            mask = rng.random((b, s)) < self.mlm_rate
            return {"tokens": np.where(mask, self.vocab_size - 1,
                                       toks).astype(np.int32),
                    "labels": np.where(mask, toks, -1).astype(np.int32)}
        return {"tokens": toks,
                "labels": np.concatenate(
                    [toks[:, 1:], np.full((b, 1), -1, np.int32)], 1)}


def make_stream(cell, model, seed: int) -> TokenStream:
    """The cell's traffic mix over `model`'s vocabulary."""
    t = cell.traffic
    return TokenStream(vocab_size=model.vocab_size, seq_len=t["seq_len"],
                       global_batch=t["global_batch"], seed=seed,
                       encoder=model.arch_type == "encoder",
                       **t.get("data", {}))
