"""The training traffic's token stream, kept with the benchmark.

A copy of the seeded Markov stream of ``repro.data.synthetic.lm_batch``:
batch ``step`` of seed ``seed`` is a pure function of the two.  The window
feeds the program its own ``lm_batch`` (the input pipeline is a layer under
test); the reference reads this copy, and the check holds the program's
batches to it token for token.
"""
from __future__ import annotations

import numpy as np

BRANCH = 16


def _successors(seed: int, vocab: int) -> np.ndarray:
    g = np.random.default_rng(np.random.SeedSequence((seed, 0xA715)))
    return g.integers(0, vocab, size=(vocab, BRANCH), dtype=np.int32)


def batch(seed: int, step: int, rows: int, seq: int, vocab: int) -> dict:
    """``{"tokens", "labels", "mask"}``, each ``(rows, seq)``: 85% of the
    tokens follow one of 16 fixed successors of the one before, 15% are
    uniform; ``labels`` are the next tokens."""
    g = np.random.default_rng(np.random.SeedSequence((seed, 0, step)))
    succ = _successors(seed, vocab)
    toks = np.empty((rows, seq + 1), np.int32)
    toks[:, 0] = g.integers(0, vocab, size=rows)
    choices = g.integers(0, BRANCH, size=(rows, seq))
    noise = g.integers(0, vocab, size=(rows, seq), dtype=np.int32)
    take_noise = g.random((rows, seq)) < 0.15
    for t in range(seq):
        nxt = succ[toks[:, t], choices[:, t]]
        toks[:, t + 1] = np.where(take_noise[:, t], noise[:, t], nxt)
    return {"tokens": toks[:, :seq], "labels": toks[:, 1:],
            "mask": np.ones((rows, seq), np.float32)}
