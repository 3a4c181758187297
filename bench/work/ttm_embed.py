"""TTM embedding lookup of ``K`` tokens from cores ``(r, v_k, h_k, r')``.

The least of two flows: chaining each token's core slices (the multiplies
of ``core/cost_model.ttm_forward_cost``, copied) or building the whole
table once (``V H r`` multiplies per chain step).  The backward is counted
as twice the forward.
"""
from __future__ import annotations


def gather_muls(core_shapes, K: int) -> int:
    rs = [s[0] for s in core_shapes] + [core_shapes[-1][-1]]
    hs = [s[2] for s in core_shapes]
    muls, h_part = 0, hs[0]
    for k in range(1, len(core_shapes)):
        muls += K * h_part * hs[k] * rs[k + 1] * rs[k]
        h_part *= hs[k]
    return muls


def build_muls(core_shapes) -> int:
    muls, v_part, h_part = 0, core_shapes[0][1], core_shapes[0][2]
    for r, v, h, r2 in core_shapes[1:]:
        v_part *= v
        h_part *= h
        muls += v_part * h_part * r * r2
    return muls


def flops(core_shapes, K: int) -> int:
    """Forward plus backward."""
    return 3 * 2 * min(gather_muls(core_shapes, K), build_muls(core_shapes))

