"""Softmax attention of ``B`` rows, ``H`` query heads over ``KV`` key/value
heads of width ``D``, ``S`` positions.

Only query-key pairs that the mask keeps are counted: ``S (S + 1) / 2``
per head when causal, ``S^2`` otherwise.  Forward: ``QK^T`` and ``PV``,
``2 D`` multiply-adds a pair each.  Backward: ``dV = P^T dO``,
``dP = dO V^T``, ``dQ = dS K``, ``dK = dS^T Q``: twice the forward, since
recomputing ``P`` does not count.  Bytes: q, k, v read and o written
(forward); q, dO, o, k, v read and dq, dk, dv written (backward), listed
in the order the kernels take and return them.  The softmax statistics
that the flash kernels pass from forward to backward are not needed by
the operation and count nothing.
"""
from __future__ import annotations


def pairs(S: int, causal: bool) -> int:
    return S * (S + 1) // 2 if causal else S * S


def forward_bytes(B, H, KV, S, D, itemsize):
    """``([q, k, v] read, [o, m, l] written)``."""
    q, kv = B * H * S * D * itemsize, B * KV * S * D * itemsize
    return [q, kv, kv], [q, 0, 0]


def backward_bytes(B, H, KV, S, D, itemsize):
    """``([q, dO, o, m, l, k, v] read, [dq, dk, dv] written)``."""
    q, kv = B * H * S * D * itemsize, B * KV * S * D * itemsize
    return [q, q, q, 0, 0, kv, kv], [q, kv, kv]


def forward(B, H, KV, S, D, causal, itemsize) -> tuple[int, int]:
    ins, outs = forward_bytes(B, H, KV, S, D, itemsize)
    return 4 * B * H * pairs(S, causal) * D, sum(ins) + sum(outs)


def backward(B, H, KV, S, D, causal, itemsize) -> tuple[int, int]:
    ins, outs = backward_bytes(B, H, KV, S, D, itemsize)
    return 8 * B * H * pairs(S, causal) * D, sum(ins) + sum(outs)
