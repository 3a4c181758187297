"""A TT linear ``y (K, M) = x (K, N) W^T`` with ``W = A B``, ``A (M, r)``
and ``B (r, N)`` the two half-factors (paper Sec. IV-B).

``mul_btt`` is paper Eq. (20), the multiplies of the bidirectional forward
(copied from ``core/cost_model.mul_btt``).  The K-dependent part, the only
part a BTT kernel computes, is ``K r (M + N)`` multiplies.

Backward, from the paper's backward equations: ``gt = gy A`` and
``gx = gt B`` (Eq. 16), ``gA = gy^T t`` and ``gB = gt^T x`` (Eqs. 10, 11)
with ``t = x B^T`` kept from the forward: four products of ``K r M`` or
``K r N`` multiplies, twice the forward's K-dependent work.

Bytes: each operand read once and each result written once, at its dtype,
listed in the order the kernel takes and returns them.
"""
from __future__ import annotations

import math


def mul_btt(ranks, out_factors, in_factors, K: int) -> int:
    """Paper Eq. (20).  ``ranks`` is ``(r_0, ..., r_2d)``."""
    rs, d = ranks, len(out_factors)
    m = (0,) + tuple(out_factors)
    n = (0,) + tuple(in_factors)
    total = 0
    for k in range(d - 1):
        t1 = rs[2 * d - k - 1] * rs[2 * d - k - 2] * math.prod(n[d - k - 1: d + 1])
        t2 = rs[k + 1] * rs[k + 2] * math.prod(m[1: k + 3])
        total += t1 + t2
    return total + K * rs[d] * (math.prod(out_factors) + math.prod(in_factors))


def forward_bytes(K: int, M: int, N: int, r: int, itemsize: int):
    """``([x, B, A] read, [y] written)``."""
    return ([K * N * itemsize, r * N * itemsize, M * r * itemsize],
            [K * M * itemsize])


def backward_bytes(K: int, M: int, N: int, r: int, itemsize: int):
    """``([x, gy, B, A] read, [gx, gA, gB] written)``."""
    return ([K * N * itemsize, K * M * itemsize, r * N * itemsize, M * r * itemsize],
            [K * N * itemsize, M * r * itemsize, r * N * itemsize])


def forward(K: int, M: int, N: int, r: int, itemsize: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the kernel forward."""
    ins, outs = forward_bytes(K, M, N, r, itemsize)
    return 2 * K * r * (M + N), sum(ins) + sum(outs)


def backward(K: int, M: int, N: int, r: int, itemsize: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the kernel backward."""
    ins, outs = backward_bytes(K, M, N, r, itemsize)
    return 4 * K * r * (M + N), sum(ins) + sum(outs)
