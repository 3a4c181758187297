"""Pallas TPU kernel: fused flash-attention backward — dQ/dK/dV in ONE
``pallas_call``.

The forward (``flash_attention.py``) keeps the online-softmax state in VMEM
so HBM never sees the S×S score matrix.  Plain autodiff through the pure-JAX
``blockwise_attention`` undoes that win for training: it saves the per-chunk
attention probabilities (S×S per head in aggregate — FTRANS identifies this
as the dominant off-chip tensor in transformer accelerators) and round-trips
the scan carry through HBM on every KV chunk.  This kernel closes the
backward half of the story: with only ``(O, m, l)`` saved by the forward, it
recomputes each probability tile from the softmax statistics in VMEM and
produces all three gradients in a single pass:

    P  = exp(S∘mask − m) / l            recomputed tile, never stored
    dV = Pᵀ dO                           accumulated per KV head
    dP = dO Vᵀ
    D  = rowsum(dO ⊙ O)                  computed in-kernel, per tile
    dS = P ∘ (dP − D)
    dQ = scale · dS K                    accumulated per Q block
    dK = scale · dSᵀ Q                   accumulated per KV head

Grid = (B·KVh, G·S/TQ, S/TK) with the KV axis innermost; axis 1 enumerates
``t = g·nq + iq`` — every (group member, Q block) pair of one KV head:

  q/do/o block (1, TQ, D)  — index ``(h·G + t//nq, t%nq)``: fetched once per
                             ``t`` (constant across the inner KV axis)
  m/l block    (1, TQ, 1) f32 — the forward's saved softmax statistics
  k/v block    (1, TK, D)  — streamed along the inner axis
  dq block     (1, TQ, D) f32 — index constant across the inner axis: the
                             block stays in VMEM, accumulates over KV steps,
                             and is flushed to HBM exactly once per ``t``
  dk/dv block  (1, S, D) f32 — index map constant in ``(t, ik)``: the WHOLE
                             per-KV-head gradient stays VMEM-resident for
                             all G·nq·nk steps of its head and flushes once
                             — the GQA head-group reduction happens in the
                             index map (``h //``-free: axis 0 *is* the KV
                             head), not by materializing repeated KV or
                             per-Q-head partials in memory.

Fully-masked blocks (causal: all ``kpos > qpos``; sliding window: all
``kpos <= qpos − w``; padded KV tail) are skipped via ``pl.when`` — the
zero-init and flush logic stays outside the gate so accumulators are
well-defined even when a row's last KV block is dead.

``choose_attn_tiles`` is the single source of truth for the launch's VMEM
residency: the kernel launches with its tiles and ``core.memory_ledger``
reports the same byte count, so ledger and launched tiles cannot drift (the
same promise ``btt_linear.choose_tiles`` / ``btt_backward.choose_bwd_tiles``
make for the TT stages).  Shapes whose working set exceeds the budget —
dK/dV residency grows with S — fall back to ``blockwise_attention`` at the
op level (``ops.flash_mha_op``).

Tiles go down to 32 rows (the f32 sublane granule) so the paper's S=32
training regime launches without sequence padding; sub-128 lane tiles are
legal for the (1, T, D) blocks (T is a sublane dim there) and Mosaic pads
the (TQ, TK) score-tile lanes in-register.

Where the whole sequence is one tile, the chooser also sets a head block
``hb > 1`` (``flash_attention.py``'s module docstring): grid = (B·H/hb,),
q/do/o/dq blocks ``(hb, S, D)``, k/v/dk/dv blocks ``(hb/G, S, D)``, m/l
blocks ``(hb, S)``, all at the arrays' own S and D.  A step holds every
query head of its KV heads, so dK/dV are summed over the group inside the
step (group members ascending, as the reference) and written once; nothing
accumulates across steps, and the gradients leave in the operands' dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .btt_linear import VMEM_BUDGET, _round_up
from .flash_attention import (
    DEFAULT_TK,
    DEFAULT_TQ,
    NEG_INF,
    batched_dot,
    repeat_heads,
    rows_mask,
)

__all__ = [
    "flash_attention_bwd_pallas",
    "choose_attn_tiles",
    "attn_bwd_vmem_fits",
    "attn_stage_vmem_bytes",
    "attn_residual_bytes",
    "attn_flops",
    "fused_attn_hbm_bytes",
    "unfused_attn_hbm_bytes",
    "DEFAULT_TQ",
    "DEFAULT_TK",
]


# ---------------------------------------------------------------------------
# Tile chooser — the single residency source for kernel, op gate, and ledger.
# ---------------------------------------------------------------------------


def _rows_vmem(hb: int, S: int, D: int, itemsize: int, group: int,
               stage: str) -> int:
    """VMEM working set of one head-block grid step: every block
    double-buffered, plus the f32 (S, S) score-shaped temporaries and f32
    products of ``hb`` heads.  Sizes are the VMEM tiles': S rounded to 32
    sublanes, D and the key axis to 128 lanes."""
    sp, dp, kp = _round_up(S, 32), _round_up(D, 128), _round_up(S, 128)
    blk, stat, kv = sp * dp * itemsize, kp * 4, hb // group
    if stage == "FWD":
        # q, k, v in; o, m, l out; the s/p score tiles and the f32 acc.
        io = (2 * hb + 2 * kv) * blk + 2 * hb * stat
        tmp = hb * (2 * sp * kp + sp * dp) * 4
    else:
        # q, do, o, m, l, k, v in; dq, dk, dv out; the s/p, dp, ds score
        # tiles and the f32 dq, dk, dv.
        io = (4 * hb + 4 * kv) * blk + 2 * hb * stat
        tmp = hb * (3 * sp * kp + 3 * sp * dp) * 4
    return 2 * io + tmp


def choose_attn_tiles(S: int, D: int, itemsize: int, *, rows: int = 1,
                      group: int = 1, tq: int | None = None,
                      tk: int | None = None, budget: int | None = None
                      ) -> tuple[int, int, int, int, int, int]:
    """(hb, tq, tk, sp, dp, vmem_bytes) for the fused attention backward.

    Tiles start at ``min(256, round_up(S, 32))`` — the 32-row granule keeps
    the paper's S=32 regime unpadded on the sequence axis — and the larger
    tile halves until the working set fits the budget.  The dk/dv residency
    (``2·sp·dp·4``) scales with S, not the tiles, so long sequences may
    never fit: callers gate on :func:`attn_bwd_vmem_fits` and fall back to
    the pure-JAX blockwise path.  (The per-step working set is independent
    of the GQA group size — the group only multiplies the grid.)

    ``hb`` is the head block, the (batch·head) rows one grid step takes.
    Where the whole sequence is one tile (``nq = nk = 1``) it is the
    largest count of the ``rows`` (B·H) that divides them, is a multiple of
    ``group`` and of 8 (the statistics' sublane tile) or all of them, and
    whose double-buffered working set fits the budget; ``vmem_bytes`` is
    then that working set.  Otherwise, or where no such count beats 1,
    ``hb = 1``: one (batch·head) pair a step, as the tiles say.
    """
    budget = budget or VMEM_BUDGET
    tq = tq or min(DEFAULT_TQ, _round_up(S, 32))
    tk = tk or min(DEFAULT_TK, _round_up(S, 32))
    dp = _round_up(D, 128)

    # q/do/o blocks + m/l + k/v blocks + dq f32 accumulator block
    # + dk/dv resident f32 accumulators + s/dp/ds (tq, tk) f32 score tiles
    def vmem(tq_, tk_):
        sp_ = _round_up(S, max(tq_, tk_))
        return (3 * tq_ * dp * itemsize + 2 * tq_ * 4
                + 2 * tk_ * dp * itemsize + tq_ * dp * 4
                + 2 * sp_ * dp * 4 + 3 * tq_ * tk_ * 4)

    while max(tq, tk) > 128 and vmem(tq, tk) > budget:
        if tq >= tk:
            tq //= 2
        else:
            tk //= 2
    sp = _round_up(S, max(tq, tk))
    if sp % tq or sp % tk:
        # Only reachable with caller-supplied tiles: auto-chosen tiles
        # start equal and halve, so each always divides the other.  A
        # non-dividing tile would silently drop tail blocks from the grid.
        raise ValueError(
            f"tiles ({tq}, {tk}) do not both divide padded S={sp}")
    if sp == tq == tk:
        for hb in range(rows, 1, -1):
            if (rows % hb == 0 and hb % group == 0
                    and (hb % 8 == 0 or hb == rows)):
                fit = _rows_vmem(hb, S, D, itemsize, group, "BWD")
                if fit <= budget:
                    return hb, tq, tk, sp, dp, fit
    return 1, tq, tk, sp, dp, vmem(tq, tk)


def attn_bwd_vmem_fits(S: int, D: int, itemsize: int, *,
                       budget: int | None = None) -> bool:
    """True iff the fused attention BWD working set fits the VMEM budget."""
    budget = budget or VMEM_BUDGET
    return choose_attn_tiles(S, D, itemsize, budget=budget)[5] <= budget


def attn_stage_vmem_bytes(S: int, D: int, itemsize: int, *, rows: int = 1,
                          group: int = 1, stage: str = "BWD",
                          fused: bool = True,
                          budget: int | None = None) -> int:
    """VMEM working set the attention stage ACTUALLY launches: the fused
    kernel's (backward-chooser-derived) when ``fused`` and it fits, else 0
    (the fallback is the pure-JAX blockwise path — no Pallas launch).
    ``rows``/``group`` (B·H and the GQA group) set the head block.
    ``core.memory_ledger`` reports exactly this number per stage."""
    if not fused or not attn_bwd_vmem_fits(S, D, itemsize, budget=budget):
        return 0
    hb, tq, tk, sp, dp, bwd_vmem = choose_attn_tiles(
        S, D, itemsize, rows=rows, group=group, budget=budget)
    if hb > 1:
        return _rows_vmem(hb, S, D, itemsize, group, stage)
    if stage == "BWD":
        return bwd_vmem
    # FWD: q + k + v + o blocks, m/l/acc scratch, one (tq, tk) score tile.
    return (2 * tq * dp * itemsize + 2 * tk * dp * itemsize
            + tq * (dp + 2) * 4 + tq * tk * 4)


def attn_residual_bytes(B: int, H: int, S: int, D: int, itemsize: int, *,
                        fused: bool) -> int:
    """Bytes ONE attention layer saves for its backward.

    Fused: ``(O, m, l)`` — O in the activation dtype plus two f32 rows of
    softmax statistics (O doubles as the o-projection's input residual, so
    charging it here over-counts — the conservative direction the ledger
    documents).  Unfused: the autodiff-saved S×S attention probabilities.
    """
    if fused:
        return B * H * S * D * itemsize + 2 * B * H * S * 4
    return B * H * S * S * itemsize


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, do_ref, o_ref, m_ref, l_ref, k_ref, v_ref,
                dq_ref, dk_ref, dv_ref, *, nq: int, nk: int, tq: int,
                tk: int, scale: float, causal: bool, window: int | None,
                s_real: int):
    """Grid (BKVh, G·nq, nk); see module docstring for block shapes."""
    t = pl.program_id(1)
    ik = pl.program_id(2)
    iq = jax.lax.rem(t, nq)

    @pl.when((t == 0) & (ik == 0))
    def _zero_dkv():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(ik == 0)
    def _zero_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    # Dead-block skipping: padded KV tail, causal (no kpos <= qpos), and
    # sliding window (no kpos > qpos - w) blocks contribute nothing.
    live = ik * tk < s_real
    if causal:
        live &= ik * tk <= iq * tq + tq - 1
    if window is not None:
        live &= ik * tk + tk - 1 > iq * tq - window

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (TQ, D)
        do = do_ref[0].astype(jnp.float32)
        o = o_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)              # (TK, D)
        v = v_ref[0].astype(jnp.float32)
        m = m_ref[0]                                  # (TQ, 1) f32
        l = l_ref[0]

        qpos = iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)

        # Scale folded into the Q operand (not a post-dot multiply): a
        # `dot*scale - m` chain invites XLA to fuse mul+sub into an FMA
        # whenever the mask `where` constant-folds away, breaking the
        # bit-for-bit single-tile contract with the reference.
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        mask = kpos < s_real
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - m) / jnp.maximum(l, 1e-30)    # normalized probs

        col = pl.multiple_of(ik * tk, tk)
        dv_ref[0, pl.ds(col, tk), :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        dp_ = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (TQ, TK)
        d_row = jnp.sum(do * o, axis=1, keepdims=True)  # D = rowsum(dO⊙O)
        # Scale folded into dS once (not into the dQ/dK epilogues, where
        # XLA could fuse it into the accumulate as an FMA and break the
        # bit-for-bit single-tile contract with the reference).
        ds = p * (dp_ - d_row) * scale

        dq_ref[0] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[0, pl.ds(col, tk), :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _rows_bwd_kernel(q_ref, do_ref, o_ref, m_ref, l_ref, k_ref, v_ref,
                     dq_ref, dk_ref, dv_ref, *, scale: float, causal: bool,
                     window: int | None, group: int):
    """One grid step of the head-block path: the ``_bwd_kernel`` step's
    products for ``hb`` whole heads at once (``nq = nk = 1``)."""
    f32, bdot = jnp.float32, batched_dot
    q = q_ref[...].astype(f32)                        # (hb, S, D)
    do = do_ref[...].astype(f32)
    o = o_ref[...].astype(f32)
    k = repeat_heads(k_ref[...].astype(f32), group)   # (hb, S, D)
    v = repeat_heads(v_ref[...].astype(f32), group)
    m = m_ref[...][..., None]                         # (hb, S, 1) f32
    l = l_ref[...][..., None]

    s = bdot(q * scale, k, (2, 2))                    # (hb, S, S)
    mask = rows_mask(s.shape, causal, window)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - m) / jnp.maximum(l, 1e-30)
    dv = bdot(p, do, (1, 1))
    dp_ = bdot(do, v, (2, 2))
    d_row = jnp.sum(do * o, axis=2, keepdims=True)
    ds = p * (dp_ - d_row) * scale
    dq_ref[...] = bdot(ds, k, (2, 1)).astype(dq_ref.dtype)
    dk = bdot(ds, q, (1, 1))

    def group_sum(x):
        # Members of one KV head are adjacent rows; add them ascending.
        if group == 1:
            return x
        x = x.reshape(x.shape[0] // group, group, *x.shape[1:])
        acc = x[:, 0]
        for g in range(1, group):
            acc = acc + x[:, g]
        return acc

    dk_ref[...] = group_sum(dk).astype(dk_ref.dtype)
    dv_ref[...] = group_sum(dv).astype(dv_ref.dtype)


def _flash_rows_bwd(q, k, v, o, m, l, do, *, causal, window, group, hb,
                    interpret):
    BH, S, D = q.shape
    BKV = k.shape[0]
    if BH % hb or hb % group:
        raise ValueError(f"head block {hb} must divide {BH} rows and be a "
                         f"multiple of the group {group}")

    def block(n):
        return pl.BlockSpec((n, S, D), lambda i: (i, 0, 0))

    stat = pl.BlockSpec((hb, S), lambda i: (i, 0))
    kv = hb // group
    return tuple(pl.pallas_call(
        functools.partial(_rows_bwd_kernel, scale=1.0 / math.sqrt(D),
                          causal=causal, window=window, group=group),
        name="flash_bwd",
        grid=(BH // hb,),
        in_specs=[block(hb), block(hb), block(hb), stat, stat,
                  block(kv), block(kv)],
        out_specs=[block(hb), block(kv), block(kv)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, S, D), v.dtype),
        ],
        # Each step owns its heads' whole gradients: no state crosses steps.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, do, o, m.astype(jnp.float32), l.astype(jnp.float32), k, v))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "group", "tq", "tk", "hb", "interpret"))
def flash_attention_bwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                               o: jax.Array, m: jax.Array, l: jax.Array,
                               do: jax.Array, *, causal: bool = True,
                               window: int | None = None, group: int = 1,
                               tq: int | None = None, tk: int | None = None,
                               hb: int = 1, interpret: bool = False
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused BWD stage: ``(dq (BH,S,D), dk, dv (BH/group,S,D))``.

    ``q/o/do (BH, S, D)``, ``m/l (BH, S)`` f32 (the forward's residuals),
    ``k/v (BH/group, S, D)``.  All dims padded to the chooser's tiles;
    padded Q rows carry ``do = 0`` so every padded contribution vanishes
    exactly.  ``hb > 1`` takes the head-block path (module docstring),
    unpadded, with ``tq``/``tk`` unused.  ``interpret=True`` runs the
    kernel body in Python on CPU — the validation path, as for every
    kernel in this package.
    """
    if hb > 1:
        return _flash_rows_bwd(q, k, v, o, m, l, do, causal=causal,
                               window=window, group=group, hb=hb,
                               interpret=interpret)
    BH, S, D = q.shape
    BKV = k.shape[0]
    scale = 1.0 / math.sqrt(D)
    itemsize = jnp.dtype(q.dtype).itemsize
    _, tq, tk, sp, dp, _ = choose_attn_tiles(S, D, itemsize, tq=tq, tk=tk)

    def pad3(x):
        return jnp.pad(x, ((0, 0), (0, sp - S), (0, dp - x.shape[2])))

    qp, dop, op = pad3(q), pad3(do), pad3(o)
    kp, vp = pad3(k), pad3(v)
    # (BH, sp, 1): a trailing unit axis keeps the (1, tq, 1) stat blocks
    # within Mosaic's block rules (see flash_attention_pallas).
    mp = jnp.pad(m.astype(jnp.float32), ((0, 0), (0, sp - S)))[..., None]
    lp = jnp.pad(l.astype(jnp.float32), ((0, 0), (0, sp - S)))[..., None]

    nq, nk = sp // tq, sp // tk
    grid = (BKV, group * nq, nk)

    def q_map(h, t, j, g=group, nq_=nq):
        return (h * g + t // nq_, t % nq_, 0)

    def stat_map(h, t, j, g=group, nq_=nq):
        return (h * g + t // nq_, t % nq_, 0)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, nq=nq, nk=nk, tq=tq, tk=tk,
                          scale=scale, causal=causal, window=window,
                          s_real=S),
        name="flash_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, dp), q_map),               # q
            pl.BlockSpec((1, tq, dp), q_map),               # do
            pl.BlockSpec((1, tq, dp), q_map),               # o
            pl.BlockSpec((1, tq, 1), stat_map),             # m
            pl.BlockSpec((1, tq, 1), stat_map),             # l
            pl.BlockSpec((1, tk, dp), lambda h, t, j: (h, j, 0)),   # k
            pl.BlockSpec((1, tk, dp), lambda h, t, j: (h, j, 0)),   # v
        ],
        out_specs=[
            pl.BlockSpec((1, tq, dp), q_map),               # dq (per-t acc)
            pl.BlockSpec((1, sp, dp), lambda h, t, j: (h, 0, 0)),   # dk
            pl.BlockSpec((1, sp, dp), lambda h, t, j: (h, 0, 0)),   # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, sp, dp), jnp.float32),
            jax.ShapeDtypeStruct((BKV, sp, dp), jnp.float32),
            jax.ShapeDtypeStruct((BKV, sp, dp), jnp.float32),
        ],
        # Axis 0 (KV heads) owns disjoint accumulators -> parallel; axes
        # 1/2 carry accumulation state (dk/dv revisit across t, dq across
        # ik) and must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, dop, op, mp, lp, kp, vp)
    return (dq[:, :S, :D].astype(q.dtype),
            dk[:, :S, :D].astype(k.dtype),
            dv[:, :S, :D].astype(v.dtype))


# ---------------------------------------------------------------------------
# Analytic FLOP / HBM-traffic models (shared by benchmarks, tests, ledger).
# ---------------------------------------------------------------------------


def _live_elems(S: int, causal: bool, window: int | None) -> int:
    """Number of unmasked (q, k) score positions."""
    if not causal and window is None:
        return S * S
    total = 0
    for i in range(S):
        lo = 0 if window is None else max(0, i - window + 1)
        hi = i if causal else S - 1
        total += max(0, hi - lo + 1)
    return total


def attn_flops(B: int, H: int, S: int, D: int, *, causal: bool = True,
               window: int | None = None) -> int:
    """FLOPs of one attention layer's fwd+bwd over the unmasked region:
    2 matmuls forward (QKᵀ, PV) + 4 backward (dV, dP, dQ, dK), each
    2·D FLOPs per live score element."""
    return B * H * _live_elems(S, causal, window) * 2 * D * 6


def fused_attn_hbm_bytes(B: int, H: int, KV: int, S: int, D: int,
                         itemsize: int, *, causal: bool = True,
                         window: int | None = None) -> int:
    """HBM bytes moved by one fused fwd + bwd launch pair (tile-derived).

    Forward: q read once, k/v refetched per (iq, ik) grid step (BlockSpec
    DMAs run even for ``pl.when``-skipped blocks), o/m/l written once.
    Backward: q/do/o/m/l read once per ``t`` (their index is constant
    across the inner KV axis), k/v refetched per step, dq written once per
    Q block, dk/dv flushed once per KV head.  No S×S tensor appears on
    either side.  Padded bytes are real bytes on the wire.  With a head
    block (``hb > 1``, batch ``B`` sets it) nothing is padded or refetched.
    """
    BH, BKV = B * H, B * KV
    hb, tq, tk, sp, dp, _ = choose_attn_tiles(S, D, itemsize, rows=BH,
                                              group=H // KV)
    if hb > 1:
        # Head blocks: every operand read once and every result written
        # once, unpadded; the gradients leave in the operands' dtype.
        qb = BH * S * D * itemsize                  # one q-shaped tensor
        kvb = BKV * S * D * itemsize                # one k-shaped tensor
        stats = 2 * BH * S * 4                      # m, l
        return (2 * qb + 2 * kvb + stats            # fwd: q, k, v, o, m, l
                + 4 * qb + 4 * kvb + stats)         # bwd: + do, dq, dk, dv
    nq, nk = sp // tq, sp // tk
    fwd = (BH * sp * dp * itemsize                  # q read once
           + BH * nq * nk * 2 * tk * dp * itemsize  # k/v refetched
           + BH * sp * dp * itemsize                # o written
           + 2 * BH * sp * 4)                       # m, l written
    bwd = (3 * BH * sp * dp * itemsize              # q, do, o read
           + 2 * BH * sp * 4                        # m, l read
           + BH * nq * nk * 2 * tk * dp * itemsize  # k/v refetched
           + BH * sp * dp * 4                       # dq written (f32)
           + 2 * BKV * sp * dp * 4)                 # dk/dv flushed once
    return fwd + bwd


def unfused_attn_hbm_bytes(B: int, H: int, KV: int, S: int, D: int,
                           itemsize: int, *, q_chunk: int = 512,
                           kv_chunk: int = 1024) -> int:
    """HBM bytes moved by ``blockwise_attention`` + plain autodiff.

    Counts, generously to XLA (each tensor once per producing/consuming
    pass, no re-reads): the raw q/k/v reads and o write; the chunk-restack
    copies (reshape+transpose into scan operands — real layout-changing
    copies, forward and again for their cotangents in backward); the
    online-softmax scan carry ``(m, l, acc)`` round-tripping HBM once per
    KV chunk (the traffic the kernel exists to kill); and the
    autodiff-saved per-chunk probabilities — S×S per head in aggregate —
    written by the forward and read back by the backward.
    """
    # Configs document 0 as "single block" (see ModelConfig.attn_q_chunk);
    # normalize the same way blockwise_attention's caller does.
    qc = min(q_chunk, S) or S
    kvc = min(kv_chunk, S) or S
    sq = _round_up(S, qc)
    skv = _round_up(S, kvc)
    nk = skv // kvc
    qkv = B * sq * H * D + 2 * B * skv * KV * D     # chunked operand elems
    raw = B * S * H * D + 2 * B * S * KV * D
    carry = 2 * nk * B * H * sq * (D + 2) * 4       # (m,l,acc) w+r per chunk
    probs = B * H * sq * skv * itemsize             # saved S×S probabilities
    fwd = (raw * itemsize + 2 * qkv * itemsize + carry + probs
           + B * S * H * D * itemsize)              # o written
    bwd = (probs + B * S * H * D * itemsize         # probs + do read
           + 3 * qkv * itemsize                     # chunk reads + cot w+r
           + carry
           + raw * 4)                               # dq/dk/dv written f32
    return fwd + bwd
