"""A tied head: logits ``(K, V)`` from features ``(K, d)`` against the
embedding table, over the logical vocabulary (padded rows are not work the
model needs).  Backward: the feature and table gradients, twice the
forward."""
from __future__ import annotations


def flops(K: int, V: int, d: int) -> int:
    """Forward plus backward."""
    return 3 * 2 * K * V * d
