"""The optimizer's update of ``n`` parameters stored at ``p_bytes`` each,
moments in float32.

SGD reads and writes p; AdamW reads and writes p, m and v.  Those are the
bytes the update needs: the state it changes persists in HBM from step to
step.  The gradient is an intermediate of the step, as the hidden state is
of an FFN block; the fused kernels take it as an operand (float32, listed
so that the kernels' operands line up) but it counts nothing, since a step
that applies the update where it makes the gradient never writes it out.
FLOPs per parameter: 2 (SGD), 12 (AdamW: two moment updates, the two bias
corrections, the square root, the division and the step).  Bytes are
listed in the order the fused kernels take and return them, after a first
operand of scalars that counts nothing.
"""
from __future__ import annotations

FLOPS = {"sgd": 2, "adamw": 12}


def work_bytes(optimizer: str, n: int, p_bytes: int):
    """``([scalars, p, (m, v,) g] read, [p, (m, v)] written)``."""
    p, f32 = n * p_bytes, n * 4
    if optimizer == "sgd":
        return [0, p, 0], [p]
    if optimizer == "adamw":
        return [0, p, f32, f32, 0], [p, f32, f32]
    raise ValueError(f"unknown optimizer {optimizer!r}")


def work(optimizer: str, n: int, p_bytes: int) -> tuple[int, int]:
    ins, outs = work_bytes(optimizer, n, p_bytes)
    return FLOPS[optimizer] * n, sum(ins) + sum(outs)
