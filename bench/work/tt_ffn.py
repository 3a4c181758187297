"""A TT FFN block as one operation: ``y = down(act(up x))`` or
``y = down(act(gate x) * up x)``, every projection a TT linear
(``bench.work.tt_linear``).

FLOPs are the sum of the projections' (the activation is not counted).
Bytes are what the block needs as a whole: x read, y written and the
half-factors read; the hidden state is an intermediate and is not counted.
The backward reads x, gy and the half-factors and writes gx and the
half-factor gradients; its FLOPs are twice the forward's.

``ranks`` are the middle ranks of ``(up, down)`` or ``(up, down, gate)``.
Bytes are listed in the order the kernels take and return them: x (and
gy), then the ``(B, A)`` pair of up, of gate, of down.
"""
from __future__ import annotations


def _pairs(d: int, f: int, ranks) -> list[tuple[int, int]]:
    """Elements of each projection's ``(B, A)``, in the kernels' order."""
    up, down = (ranks[0], d, f), (ranks[1], f, d)
    order = [up, (ranks[2], d, f), down] if len(ranks) == 3 else [up, down]
    return [(r * n_in, n_out * r) for r, n_in, n_out in order]


def forward_bytes(K: int, d: int, f: int, ranks, itemsize: int):
    """``([x, B, A of each projection] read, [y] written)``."""
    factors = [e * itemsize for pair in _pairs(d, f, ranks) for e in pair]
    return [K * d * itemsize] + factors, [K * d * itemsize]


def backward_bytes(K: int, d: int, f: int, ranks, itemsize: int):
    """``([x, gy, B, A of each projection] read, [gx, gA, gB of each
    projection] written)``."""
    pairs = _pairs(d, f, ranks)
    ins = [e * itemsize for pair in pairs for e in pair]
    outs = [e * itemsize for b, a in pairs for e in (a, b)]
    return [K * d * itemsize] * 2 + ins, [K * d * itemsize] + outs


def forward(K: int, d: int, f: int, ranks, itemsize: int) -> tuple[int, int]:
    ins, outs = forward_bytes(K, d, f, ranks, itemsize)
    return sum(2 * K * r * (d + f) for r in ranks), sum(ins) + sum(outs)


def backward(K: int, d: int, f: int, ranks, itemsize: int) -> tuple[int, int]:
    ins, outs = backward_bytes(K, d, f, ranks, itemsize)
    return sum(4 * K * r * (d + f) for r in ranks), sum(ins) + sum(outs)
