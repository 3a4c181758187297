"""Softmax attention of ``B`` rows, ``H`` query heads over ``KV`` key/value
heads, ``S`` positions; queries and keys of width ``D``, values (and so
the output) of width ``Dv``, which is ``D`` unless given (latent attention
has keys of 192 and values of 128).

Only query-key pairs that the mask keeps are counted: ``S (S + 1) / 2``
per head when causal, ``S^2`` otherwise.  Forward: ``QK^T`` at ``2 D`` and
``PV`` at ``2 Dv`` FLOPs a pair.  Backward: ``dV = P^T dO`` and
``dP = dO V^T`` at ``2 Dv`` each, ``dQ = dS K`` and ``dK = dS^T Q`` at
``2 D`` each: twice the forward, since recomputing ``P`` does not count.
Bytes: q, k, v read and o written (forward); q, dO, o, k, v read and dq,
dk, dv written (backward), listed in the order the kernels take and return
them.  The softmax statistics that the flash kernels pass from forward to
backward are not needed by the operation and count nothing.
"""
from __future__ import annotations


def pairs(S: int, causal: bool) -> int:
    return S * (S + 1) // 2 if causal else S * S


def _sizes(B, H, KV, S, D, Dv, itemsize):
    """Bytes of one q (or dq), k, v and o (or dO) tensor."""
    Dv = D if Dv is None else Dv
    return (B * H * S * D * itemsize, B * KV * S * D * itemsize,
            B * KV * S * Dv * itemsize, B * H * S * Dv * itemsize)


def forward_bytes(B, H, KV, S, D, itemsize, Dv=None):
    """``([q, k, v] read, [o, m, l] written)``."""
    q, k, v, o = _sizes(B, H, KV, S, D, Dv, itemsize)
    return [q, k, v], [o, 0, 0]


def backward_bytes(B, H, KV, S, D, itemsize, Dv=None):
    """``([q, dO, o, m, l, k, v] read, [dq, dk, dv] written)``."""
    q, k, v, o = _sizes(B, H, KV, S, D, Dv, itemsize)
    return [q, o, o, 0, 0, k, v], [q, k, v]


def forward(B, H, KV, S, D, causal, itemsize, Dv=None) -> tuple[int, int]:
    ins, outs = forward_bytes(B, H, KV, S, D, itemsize, Dv)
    Dv = D if Dv is None else Dv
    return 2 * B * H * pairs(S, causal) * (D + Dv), sum(ins) + sum(outs)


def backward(B, H, KV, S, D, causal, itemsize, Dv=None) -> tuple[int, int]:
    ins, outs = backward_bytes(B, H, KV, S, D, itemsize, Dv)
    Dv = D if Dv is None else Dv
    return 4 * B * H * pairs(S, causal) * (D + Dv), sum(ins) + sum(outs)
