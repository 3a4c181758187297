"""Deterministic seekable LM token stream (generic arch shapes).

Batches are pure functions of ``(seed, step)`` via counter-based RNG
(numpy ``SeedSequence((seed, step))``): skip-ahead restart and multi-host
determinism come for free.  Tokens follow a Zipf-ish marginal with a
first-order Markov structure so perplexity is learnable (loss decreases),
which the integration tests assert.
"""
from __future__ import annotations

import numpy as np

from repro.tracing import span

__all__ = ["lm_batch", "lm_eval_batch"]


def _rng(seed: int, step: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence((seed, stream, step)))


def _markov_tables(seed: int, vocab: int, branch: int = 16):
    """Fixed per-seed Markov structure: each token has ``branch`` likely
    successors.  Cached per (seed, vocab)."""
    key = (seed, vocab, branch)
    tbl = _markov_tables._cache.get(key)
    if tbl is None:
        g = np.random.default_rng(np.random.SeedSequence((seed, 0xA715)))
        succ = g.integers(0, vocab, size=(vocab, branch), dtype=np.int32)
        tbl = succ
        _markov_tables._cache[key] = tbl
    return tbl


_markov_tables._cache = {}


def lm_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int,
             *, stream: int = 0) -> dict:
    """One global batch: {"tokens" (B,S), "labels" (B,S), "mask" (B,S)}.

    labels[t] = tokens[t+1] (next-token prediction); final position masked.
    Runs under the host span ``data.lm_batch`` (``repro.tracing``).
    """
    with span("data.lm_batch"):
        g = _rng(seed, step, stream)
        succ = _markov_tables(seed, vocab)
        branch = succ.shape[1]
        toks = np.empty((batch, seq_len + 1), np.int32)
        toks[:, 0] = g.integers(0, vocab, size=batch)
        # 85% Markov successor, 15% uniform noise — learnable but not trivial.
        choices = g.integers(0, branch, size=(batch, seq_len))
        noise = g.integers(0, vocab, size=(batch, seq_len), dtype=np.int32)
        take_noise = g.random((batch, seq_len)) < 0.15
        for t in range(seq_len):
            nxt = succ[toks[:, t], choices[:, t]]
            toks[:, t + 1] = np.where(take_noise[:, t], noise[:, t], nxt)
        mask = np.ones((batch, seq_len), np.float32)
        return {
            "tokens": toks[:, :seq_len],
            "labels": toks[:, 1:],
            "mask": mask,
        }


def lm_eval_batch(seed: int, step: int, batch: int, seq_len: int,
                  vocab: int) -> dict:
    """Held-out stream (disjoint RNG stream from training)."""
    return lm_batch(seed, step, batch, seq_len, vocab, stream=1)
