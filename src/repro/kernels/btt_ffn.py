"""Pallas TPU kernels: fused tensorized-FFN megakernel (FWD + BWD).

The FFN block is the widest thing the model computes: its hidden state is
``(K, d_ff)`` — 4x wider than anything attention touches on the usual
``d_ff = 4 d_model`` configs.  Executed as two (three when gated) separate
``btt_linear_op`` calls, that hidden state round-trips HBM twice per layer
in the forward (written by the up projection, re-read by the down
projection) and again in the backward (saved as the down projection's
input residual, re-read by its backward launch) — exactly the off-chip
traffic the paper's intra-layer MUL1/MUL2 pipelining eliminates (Sec. V),
and the FlashAttention-style producer/consumer locality argument applied
to the paper's bidirectional contraction.

This module runs the whole block as ONE ``pallas_call`` per direction:

    y = A2 @ (B2 @ act(A1 @ (B1 @ x)))                       (ungated)
    y = A2 @ (B2 @ (act(Ag @ (Bg @ x)) * A1 @ (B1 @ x)))     (gated)

Tiling (BlockSpec; grid = (K/TK,), one K row-block per grid step):

  x block    (TK, NP)      — streamed from HBM, read ONCE per direction
  y/gx block (TK, MP/NP)   — streamed out, written once
  B1 (R1P, NP), A1 (FP, R1P), B2 (R2P, FP), A2 (MP, R2P)
  [Bg (RgP, NP), Ag (FP, RgP)]
             — every half-factor fully VMEM-resident (constant index map;
               LoRETTA's observation: the low-rank half-factor structure
               is what makes whole-block fusion feasible — A/B are tiny)
  h scratch  (TK, FP)      — the hidden tile.  It NEVER leaves VMEM: the
                             down contraction consumes it in the same grid
                             step that produced it.
  gA*/gB* blocks (f32)     — backward only: constant-index-map output
                             accumulators, flushed to HBM exactly once
                             (the revisiting-accumulator pattern of
                             ``btt_backward.py``).

The backward recomputes the hidden tile (and the gate pre-activation)
from ``x`` inside the kernel, so the block's training residual shrinks
from ``(K, d_ff)`` + gate pre-activations to just ``x`` — O(K·d_model).

Every contraction mirrors the two-call path's exact GEMM + cast sequence
(``btt_linear_pallas`` / ``btt_backward_pallas``), so on unpadded
single-tile shapes the kernel is bit-identical to the two-call reference
(asserted in tests/test_btt_ffn.py).  Shapes whose working set exceeds the
VMEM budget (``ffn_vmem_fits``) fall back to the two-call path in
``ops.py``; ``core.memory_ledger`` gates its FFN rows on the same
predicate, so ledger and dispatch cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .btt_linear import (
    DEFAULT_TK,
    VMEM_BUDGET,
    _round_up,
    _sublane as _decode_sublane,
    choose_tiles,
)

__all__ = [
    "btt_ffn_pallas",
    "btt_ffn_bwd_pallas",
    "choose_ffn_tiles",
    "ffn_vmem_fits",
    "ffn_stage_vmem_bytes",
    "ffn_residual_bytes",
    "fused_ffn_hbm_bytes",
    "unfused_ffn_hbm_bytes",
    "ffn_flops",
    "btt_ffn_decode_pallas",
    "choose_decode_ffn_tiles",
    "decode_ffn_vmem_fits",
    "decode_ffn_stage_vmem_bytes",
    "fused_decode_ffn_hbm_bytes",
    "unfused_decode_ffn_hbm_bytes",
]

ACTS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu}


# ---------------------------------------------------------------------------
# Tile chooser — the single residency source for kernel, ledger and op gate.
# ---------------------------------------------------------------------------


def choose_ffn_tiles(M: int, N: int, F: int, R1: int, R2: int, Rg: int,
                     itemsize: int, *, tk: int | None = None,
                     K: int | None = None
                     ) -> tuple[int, int, int, int, int, int, int, int, int]:
    """(tk, mp, np, fp, r1p, r2p, rgp, fwd_vmem, bwd_vmem) for the fused FFN.

    ``M``/``N`` are the down/up projections' model dims (both d_model on
    every shipped config), ``F`` the hidden dim, ``R*`` the mid-ranks;
    ``Rg = 0`` means ungated.  Single source of truth for the megakernel's
    residency: both kernels launch with these tiles, ``ffn_vmem_fits``
    gates the op on the (larger) BWD working set, and
    ``core.memory_ledger`` reports the same numbers — the three cannot
    drift (the FWD/BWD/ATTN stages make the identical promise through
    their own choosers).

    ``K`` caps ``tk`` at the sublane-aligned row count actually present
    (paper regime: K=32).  The half-factor blocks and the f32 gradient
    accumulators do not scale with ``tk``, so oversized layers (d_ff in
    the thousands) may never fit — callers gate on :func:`ffn_vmem_fits`
    and fall back to the two-call path.
    """
    tk = tk or DEFAULT_TK
    if K is not None:
        tk = min(tk, _round_up(K, 32))  # 32: every dtype's sublane tile
    mp = _round_up(M, 128)
    np_ = _round_up(N, 128)
    fp = _round_up(F, 128)
    r1p = _round_up(R1, 128)
    r2p = _round_up(R2, 128)
    rgp = _round_up(Rg, 128) if Rg else 0
    n_hidden = 3 if Rg else 2  # h + u (+ g) hidden-width scratch tiles

    # All half-factors resident for the whole launch.
    hf = (r1p * np_ + fp * r1p + r2p * fp + mp * r2p
          + (rgp * np_ + fp * rgp)) * itemsize
    # BWD-only f32 accumulator blocks (constant index maps).
    acc = (fp * r1p + r1p * np_ + mp * r2p + r2p * fp
           + (fp * rgp + rgp * np_)) * 4

    def fwd(tk_):
        return (tk_ * np_ * itemsize + tk_ * mp * itemsize + hf
                + tk_ * fp * itemsize        # h scratch tile
                + tk_ * fp * 4               # f32 hidden temp (pre-cast)
                + tk_ * (r1p + r2p + rgp) * 4)  # rank-width f32 temps

    def bwd(tk_):
        return (2 * tk_ * np_ * itemsize     # x in, gx out
                + tk_ * mp * itemsize        # gy
                + hf + acc
                + n_hidden * tk_ * fp * itemsize   # h/u(/g) scratch tiles
                + 2 * tk_ * fp * 4                 # gh/gu f32 temps
                + 2 * tk_ * (r1p + r2p + rgp) * 4)  # t/gt rank-width temps

    # Shrink toward the 32-row floor keeping every intermediate size
    # 32-aligned (tk starts at a multiple of 32 but is not in general a
    # power of two — plain halving could yield 48- or 24-row blocks,
    # breaking the bf16 sublane tile on a real TPU).
    while tk > 32 and bwd(tk) > VMEM_BUDGET:
        tk = max(32, _round_up(tk // 2, 32))
    return tk, mp, np_, fp, r1p, r2p, rgp, fwd(tk), bwd(tk)


def ffn_vmem_fits(M: int, N: int, F: int, R1: int, R2: int, Rg: int,
                  itemsize: int, K: int | None = None) -> bool:
    """True iff the fused FFN's (BWD, the larger) working set fits VMEM.

    THE dispatch predicate: ``ops.btt_ffn_op`` takes the megakernel path
    iff this holds, and the memory ledger's ffn rows gate on it too.
    """
    tiles = choose_ffn_tiles(M, N, F, R1, R2, Rg, itemsize, K=K)
    return max(tiles[7], tiles[8]) <= VMEM_BUDGET


def ffn_stage_vmem_bytes(M: int, N: int, F: int, R1: int, R2: int, Rg: int,
                         itemsize: int, *, K: int | None = None,
                         stage: str = "FWD", fused: bool = True) -> int:
    """VMEM working set of the FFN-stage megakernel launch, or 0 when the
    block runs the two-call path (``fused=False`` or over budget — there
    the per-linear launches are charged under the existing kernel rows)."""
    if not fused or not ffn_vmem_fits(M, N, F, R1, R2, Rg, itemsize, K=K):
        return 0
    tiles = choose_ffn_tiles(M, N, F, R1, R2, Rg, itemsize, K=K)
    return tiles[7] if stage == "FWD" else tiles[8]


def ffn_residual_bytes(K: int, F: int, itemsize: int, *,
                       gated: bool, fused: bool) -> int:
    """Training residual of ONE FFN block application beyond the saved
    layer input ``x``: the act pre-activations (u, and g when gated) plus
    the down projection's saved input ``h`` on the two-call path; nothing
    with the megakernel (it recomputes the hidden tile from ``x``)."""
    if fused:
        return 0
    n_pre = 2 if gated else 1
    return (n_pre + 1) * K * F * itemsize


# ---------------------------------------------------------------------------
# Kernel bodies.
# ---------------------------------------------------------------------------


def _mask_cols(v: jax.Array, f_logical: int) -> jax.Array:
    """Zero columns >= f_logical (real half-factor rows past the logical
    d_ff — the two-call path slices them away between the calls)."""
    if f_logical >= v.shape[1]:
        return v
    cols = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where(cols < f_logical, v, jnp.zeros_like(v))


def _dot(x, w, dims, out=jnp.float32):
    return jax.lax.dot_general(x, w, dimension_numbers=(dims, ((), ())),
                               preferred_element_type=out)


def _half_linear(x, b, a, out_dtype):
    """One BTT linear exactly as ``btt_linear_pallas`` computes it:
    ``t = x @ b^T`` (f32), ``y = (t cast) @ a^T`` (f32, cast to out)."""
    t = _dot(x, b, ((1,), (1,)))
    y = _dot(t.astype(a.dtype), a, ((1,), (1,)))
    return t, y.astype(out_dtype)


def _hidden(x, b1, a1, bg, ag, act: str, f_logical: int, dt):
    """Recompute the block's hidden tile (and everything needed for its
    VJP) from x: returns (t1, u, tg, g, h) — tg/g None when ungated."""
    t1, u = _half_linear(x, b1, a1, dt)
    if bg is not None:
        tg, g = _half_linear(x, bg, ag, dt)
        h = ACTS[act](g) * u
    else:
        tg = g = None
        h = ACTS[act](u)
    return t1, u, tg, g, _mask_cols(h, f_logical)


def _deq_operands(s_ref, x_ref, factor_refs):
    """Dequantize the FFN operand refs into f32 VMEM values: x at scale
    slot 0, half-factors at their fixed slots [b1, a1, bg, ag, b2, a2] =
    s[1..6] (gate slots unused when ungated).  The low-precision tiles are
    upcast HERE, in VMEM — the dense f32 tensors never exist in HBM."""
    x = x_ref[...].astype(jnp.float32) * s_ref[0, 0]
    facs = [r[...].astype(jnp.float32) * s_ref[0, 1 + i] if r is not None
            else None for i, r in enumerate(factor_refs)]
    return x, facs


def _ffn_fwd_kernel(*refs, act: str, f_logical: int, gated: bool,
                    quant: bool):
    """Grid (nK,); see module docstring for block shapes."""
    if quant:
        s_ref, *refs = refs
    if gated:
        x_ref, b1_ref, a1_ref, bg_ref, ag_ref, b2_ref, a2_ref, \
            y_ref, h_ref = refs
    else:
        x_ref, b1_ref, a1_ref, b2_ref, a2_ref, y_ref, h_ref = refs
        bg_ref = ag_ref = None

    dt = y_ref.dtype
    if quant:
        x, (b1, a1, bg, ag, b2, a2) = _deq_operands(
            s_ref, x_ref, (b1_ref, a1_ref, bg_ref, ag_ref, b2_ref, a2_ref))
    else:
        x, b1, a1, b2, a2 = (x_ref[...], b1_ref[...], a1_ref[...],
                             b2_ref[...], a2_ref[...])
        bg = bg_ref[...] if gated else None
        ag = ag_ref[...] if gated else None
    _, _, _, _, h = _hidden(x, b1, a1, bg, ag, act, f_logical, dt)
    h_ref[...] = h  # VMEM scratch: produced and consumed in this grid step
    _, y = _half_linear(h_ref[...], b2, a2, y_ref.dtype)
    y_ref[...] = y


def _ffn_bwd_kernel(*refs, act: str, f_logical: int, gated: bool,
                    quant: bool):
    """Grid (nK,): recompute the hidden tile from x, then run the whole
    block's VJP with ga/gb accumulated in VMEM-resident f32 blocks.  In
    quant mode operands dequantize at entry and the gradients are those
    of the dequantized operands (straight-through)."""
    if quant:
        s_ref, *refs = refs
    if gated:
        (x_ref, gy_ref, b1_ref, a1_ref, bg_ref, ag_ref, b2_ref, a2_ref,
         gx_ref, ga1_ref, gb1_ref, gag_ref, gbg_ref, ga2_ref, gb2_ref,
         h_ref, u_ref, g_ref) = refs
    else:
        (x_ref, gy_ref, b1_ref, a1_ref, b2_ref, a2_ref,
         gx_ref, ga1_ref, gb1_ref, ga2_ref, gb2_ref,
         h_ref, u_ref) = refs
        bg_ref = ag_ref = gag_ref = gbg_ref = g_ref = None

    k = pl.program_id(0)

    @pl.when(k == 0)
    def _zero_accumulators():
        for r in (ga1_ref, gb1_ref, ga2_ref, gb2_ref, gag_ref, gbg_ref):
            if r is not None:
                r[...] = jnp.zeros_like(r)

    dt = gx_ref.dtype
    if quant:
        x, (b1, a1, bg, ag, b2, a2) = _deq_operands(
            s_ref, x_ref, (b1_ref, a1_ref, bg_ref, ag_ref, b2_ref, a2_ref))
    else:
        x, b1, a1, b2, a2 = (x_ref[...], b1_ref[...], a1_ref[...],
                             b2_ref[...], a2_ref[...])
        bg = bg_ref[...] if gated else None
        ag = ag_ref[...] if gated else None
    gy = gy_ref[...]

    # Recompute the forward up to the hidden tile (paper-style: residuals
    # are x only; the hidden state never existed in HBM to reload).
    t1, u, tg, g, h = _hidden(x, b1, a1, bg, ag, act, f_logical, dt)
    h_ref[...] = h
    u_ref[...] = u
    if gated:
        g_ref[...] = g

    # Down-projection backward (btt_backward's exact contraction set with
    # x := h): t2 recomputed, gh streamed to the act VJP, ga2/gb2
    # accumulated f32.
    t2 = _dot(h_ref[...], b2, ((1,), (1,)))
    gt2 = _dot(gy, a2, ((1,), (0,)))
    gh = _dot(gt2.astype(b2.dtype), b2, ((1,), (0,))).astype(dt)
    ga2_ref[...] += _dot(gy.astype(jnp.float32), t2, ((0,), (0,)))
    gb2_ref[...] += _dot(gt2, h_ref[...].astype(jnp.float32), ((0,), (0,)))

    # Activation VJP — autodiff of the exact expression the two-call path
    # differentiates, on the recomputed pre-activations.
    if gated:
        _, act_vjp = jax.vjp(lambda gg, uu: ACTS[act](gg) * uu,
                             g_ref[...], u_ref[...])
        gg_, gu = act_vjp(gh)
        gg_ = _mask_cols(gg_, f_logical)
    else:
        _, act_vjp = jax.vjp(ACTS[act], u_ref[...])
        (gu,) = act_vjp(gh)
        gg_ = None
    gu = _mask_cols(gu, f_logical)

    # Up (and gate) projection backward; gx summed across branches in the
    # storage dtype, as autodiff sums the two x-cotangents.
    gt1 = _dot(gu, a1, ((1,), (0,)))
    gx = _dot(gt1.astype(b1.dtype), b1, ((1,), (0,))).astype(dt)
    ga1_ref[...] += _dot(gu.astype(jnp.float32), t1, ((0,), (0,)))
    gb1_ref[...] += _dot(gt1, x.astype(jnp.float32), ((0,), (0,)))
    if gated:
        gtg = _dot(gg_, ag, ((1,), (0,)))
        gx = gx + _dot(gtg.astype(bg.dtype), bg, ((1,), (0,))).astype(dt)
        gag_ref[...] += _dot(gg_.astype(jnp.float32), tg, ((0,), (0,)))
        gbg_ref[...] += _dot(gtg, x.astype(jnp.float32), ((0,), (0,)))
    gx_ref[...] = gx


# ---------------------------------------------------------------------------
# Launch wrappers.
# ---------------------------------------------------------------------------


def _pad2(v, r, c):
    return jnp.pad(v, ((0, r - v.shape[0]), (0, c - v.shape[1])))


def _dims(x, gy, b1, a1, b2, a2, bg):
    K, N = x.shape
    R1, _ = b1.shape
    F, _ = a1.shape
    R2, _ = b2.shape
    M, _ = a2.shape
    Rg = bg.shape[0] if bg is not None else 0
    return K, N, F, M, R1, R2, Rg


def _ffn_itemsize(x, factors) -> int:
    return max(jnp.dtype(v.dtype).itemsize
               for v in (x, *[f for f in factors if f is not None]))


@functools.partial(jax.jit, static_argnames=("act", "f_logical", "tk",
                                             "interpret", "out_dtype"))
def btt_ffn_pallas(x: jax.Array, b1: jax.Array, a1: jax.Array,
                   b2: jax.Array, a2: jax.Array,
                   bg: jax.Array | None = None, ag: jax.Array | None = None,
                   *, act: str = "gelu", f_logical: int | None = None,
                   scales: jax.Array | None = None, out_dtype=None,
                   tk: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """Fused FFN forward: ``x (K, N) -> y (K, M)`` through both (three when
    ``bg``/``ag`` given) TT half-factor pairs and the activation, with the
    ``(TK, F)`` hidden tile living only in VMEM scratch.

    ``f_logical`` is the logical d_ff (< F when ``factorize`` padded the
    hidden dim): hidden columns past it are zeroed, exactly what the
    two-call path's slice-then-repad does.  Padding to hardware tiles is
    exact for every contraction here (``act(0) = 0`` for gelu/silu, so
    padded hidden columns contribute nothing through the zero-padded B2).

    ``scales`` (a (1, 8) f32 ``[s_x, s_b1, s_a1, s_bg, s_ag, s_b2, s_a2,
    pad]``) switches to the quantized-operand kernel: operands stream in
    storage dtypes and dequantize at kernel entry in VMEM; ``out_dtype``
    then names the compute dtype of ``y`` and the hidden scratch.
    """
    gated = bg is not None
    K, N, F, M, R1, R2, Rg = _dims(x, None, b1, a1, b2, a2, bg)
    if f_logical is None:
        f_logical = F
    out_dtype = out_dtype or x.dtype
    itemsize = _ffn_itemsize(x, (b1, a1, b2, a2, bg, ag))
    tk, mp, np_, fp, r1p, r2p, rgp, _, _ = choose_ffn_tiles(
        M, N, F, R1, R2, Rg, itemsize, tk=tk, K=K)

    kp = _round_up(K, tk)
    xp = jnp.pad(x, ((0, kp - K), (0, np_ - N)))
    ops_ = [xp, _pad2(b1, r1p, np_), _pad2(a1, fp, r1p)]
    in_specs = [
        pl.BlockSpec((tk, np_), lambda k: (k, 0)),   # x
        pl.BlockSpec((r1p, np_), lambda k: (0, 0)),  # b1 (resident)
        pl.BlockSpec((fp, r1p), lambda k: (0, 0)),   # a1 (resident)
    ]
    if gated:
        ops_ += [_pad2(bg, rgp, np_), _pad2(ag, fp, rgp)]
        in_specs += [
            pl.BlockSpec((rgp, np_), lambda k: (0, 0)),  # bg (resident)
            pl.BlockSpec((fp, rgp), lambda k: (0, 0)),   # ag (resident)
        ]
    ops_ += [_pad2(b2, r2p, fp), _pad2(a2, mp, r2p)]
    in_specs += [
        pl.BlockSpec((r2p, fp), lambda k: (0, 0)),   # b2 (resident)
        pl.BlockSpec((mp, r2p), lambda k: (0, 0)),   # a2 (resident)
    ]
    if scales is not None:
        ops_ = [scales.astype(jnp.float32).reshape(1, 8)] + ops_
        in_specs = [pl.BlockSpec((1, 8), lambda k: (0, 0),
                                 memory_space=pltpu.SMEM)] + in_specs

    y = pl.pallas_call(
        functools.partial(_ffn_fwd_kernel, act=act, f_logical=f_logical,
                          gated=gated, quant=scales is not None),
        name="btt_ffn_fwd",
        grid=(kp // tk,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tk, mp), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, mp), out_dtype),
        scratch_shapes=[pltpu.VMEM((tk, fp), out_dtype)],  # the hidden tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*ops_)
    return y[:K, :M]


@functools.partial(jax.jit, static_argnames=("act", "f_logical", "tk",
                                             "interpret", "out_dtype"))
def btt_ffn_bwd_pallas(x: jax.Array, gy: jax.Array, b1: jax.Array,
                       a1: jax.Array, b2: jax.Array, a2: jax.Array,
                       bg: jax.Array | None = None,
                       ag: jax.Array | None = None, *, act: str = "gelu",
                       f_logical: int | None = None,
                       scales: jax.Array | None = None, out_dtype=None,
                       tk: int | None = None,
                       interpret: bool = False) -> tuple:
    """Fused FFN backward from ``x`` and ``gy`` ONLY (the hidden tile and
    gate pre-activation are recomputed in VMEM): returns
    ``(gx, ga1, gb1, ga2, gb2)`` — plus ``(gag, gbg)`` appended when gated
    — with all half-factor gradients accumulated and returned in f32 (the
    final cast to the core dtype happens once, in ``ops.py``).

    ``scales``/``out_dtype`` as in :func:`btt_ffn_pallas`: quantized
    operands dequantize at kernel entry and the gradients returned are
    those of the dequantized operands (straight-through)."""
    gated = bg is not None
    K, N, F, M, R1, R2, Rg = _dims(x, gy, b1, a1, b2, a2, bg)
    if f_logical is None:
        f_logical = F
    out_dtype = out_dtype or x.dtype
    itemsize = _ffn_itemsize(x, (gy, b1, a1, b2, a2, bg, ag))
    tk, mp, np_, fp, r1p, r2p, rgp, _, _ = choose_ffn_tiles(
        M, N, F, R1, R2, Rg, itemsize, tk=tk, K=K)

    kp = _round_up(K, tk)
    ops_ = [jnp.pad(x, ((0, kp - K), (0, np_ - N))),
            jnp.pad(gy, ((0, kp - K), (0, mp - M))),
            _pad2(b1, r1p, np_), _pad2(a1, fp, r1p)]
    in_specs = [
        pl.BlockSpec((tk, np_), lambda k: (k, 0)),   # x
        pl.BlockSpec((tk, mp), lambda k: (k, 0)),    # gy
        pl.BlockSpec((r1p, np_), lambda k: (0, 0)),  # b1 (resident)
        pl.BlockSpec((fp, r1p), lambda k: (0, 0)),   # a1 (resident)
    ]
    if gated:
        ops_ += [_pad2(bg, rgp, np_), _pad2(ag, fp, rgp)]
        in_specs += [
            pl.BlockSpec((rgp, np_), lambda k: (0, 0)),
            pl.BlockSpec((fp, rgp), lambda k: (0, 0)),
        ]
    ops_ += [_pad2(b2, r2p, fp), _pad2(a2, mp, r2p)]
    in_specs += [
        pl.BlockSpec((r2p, fp), lambda k: (0, 0)),
        pl.BlockSpec((mp, r2p), lambda k: (0, 0)),
    ]

    if scales is not None:
        ops_ = [scales.astype(jnp.float32).reshape(1, 8)] + ops_
        in_specs = [pl.BlockSpec((1, 8), lambda k: (0, 0),
                                 memory_space=pltpu.SMEM)] + in_specs

    out_specs = [
        pl.BlockSpec((tk, np_), lambda k: (k, 0)),   # gx (streamed)
        pl.BlockSpec((fp, r1p), lambda k: (0, 0)),   # ga1 (accumulator)
        pl.BlockSpec((r1p, np_), lambda k: (0, 0)),  # gb1 (accumulator)
    ]
    out_shape = [
        jax.ShapeDtypeStruct((kp, np_), out_dtype),
        jax.ShapeDtypeStruct((fp, r1p), jnp.float32),
        jax.ShapeDtypeStruct((r1p, np_), jnp.float32),
    ]
    if gated:
        out_specs += [
            pl.BlockSpec((fp, rgp), lambda k: (0, 0)),   # gag
            pl.BlockSpec((rgp, np_), lambda k: (0, 0)),  # gbg
        ]
        out_shape += [
            jax.ShapeDtypeStruct((fp, rgp), jnp.float32),
            jax.ShapeDtypeStruct((rgp, np_), jnp.float32),
        ]
    out_specs += [
        pl.BlockSpec((mp, r2p), lambda k: (0, 0)),   # ga2
        pl.BlockSpec((r2p, fp), lambda k: (0, 0)),   # gb2
    ]
    out_shape += [
        jax.ShapeDtypeStruct((mp, r2p), jnp.float32),
        jax.ShapeDtypeStruct((r2p, fp), jnp.float32),
    ]

    scratch = [pltpu.VMEM((tk, fp), out_dtype),   # h
               pltpu.VMEM((tk, fp), out_dtype)]   # u
    if gated:
        scratch.append(pltpu.VMEM((tk, fp), out_dtype))  # g

    outs = pl.pallas_call(
        functools.partial(_ffn_bwd_kernel, act=act, f_logical=f_logical,
                          gated=gated, quant=scales is not None),
        name="btt_ffn_bwd",
        grid=(kp // tk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        # The K axis carries accumulation state (ga/gb revisit every step).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*ops_)

    if gated:
        gx, ga1, gb1, gag, gbg, ga2, gb2 = outs
        return (gx[:K, :N], ga1[:F, :R1], gb1[:R1, :N],
                ga2[:M, :R2], gb2[:R2, :F], gag[:F, :Rg], gbg[:Rg, :N])
    gx, ga1, gb1, ga2, gb2 = outs
    return (gx[:K, :N], ga1[:F, :R1], gb1[:R1, :N],
            ga2[:M, :R2], gb2[:R2, :F])


# ---------------------------------------------------------------------------
# Analytic HBM-traffic / FLOP models (shared by benchmarks and tests).
# ---------------------------------------------------------------------------


def ffn_flops(K: int, M: int, N: int, F: int, R1: int, R2: int,
              Rg: int = 0) -> int:
    """MACs x2 of the block's GEMMs, forward + backward (activation VPU
    work excluded — identical on both paths)."""
    from .btt_backward import bwd_flops

    fwd = 2 * K * (R1 * (N + F) + R2 * (F + M) + Rg * (N + F))
    bwd = bwd_flops(K, F, N, R1) + bwd_flops(K, M, F, R2)
    if Rg:
        bwd += bwd_flops(K, F, N, Rg)
    return fwd + bwd


def _hf_elems(np_, mp, fp, r1p, r2p, rgp):
    return (r1p * np_ + fp * r1p + r2p * fp + mp * r2p
            + rgp * np_ + fp * rgp)


def fused_ffn_hbm_bytes(K: int, M: int, N: int, F: int, R1: int, R2: int,
                        Rg: int, itemsize: int) -> int:
    """HBM bytes of one fused fwd + one fused bwd launch (tile-derived).

    Reads: x once per direction, gy once, every half-factor once per
    launch (constant index maps — Pallas fetches a revisited block once).
    Writes: y, gx, and the single end-of-grid flush of the f32 gradient
    accumulators.  The hidden state appears on NEITHER side — it never
    exists in HBM.  Counts are over padded dims (padded bytes are real
    bytes on the wire).
    """
    tk, mp, np_, fp, r1p, r2p, rgp, _, _ = choose_ffn_tiles(
        M, N, F, R1, R2, Rg, itemsize, K=K)
    kp = _round_up(K, tk)
    hf = _hf_elems(np_, mp, fp, r1p, r2p, rgp)
    fwd = (kp * np_ + hf) * itemsize + kp * mp * itemsize
    bwd = ((kp * np_ + kp * mp + hf) * itemsize   # x, gy, half-factors
           + kp * np_ * itemsize                   # gx
           + hf * 4)                               # f32 grad flush
    return fwd + bwd


def _fwd_launch_bytes(K: int, M: int, N: int, R: int, itemsize: int) -> int:
    """HBM traffic of one ``btt_linear_pallas`` launch (its own tiles):
    x streamed once, the b operand refetched per K row-block, a fetched
    once, y written once."""
    tkf, tnf, mp, rp, _ = choose_tiles(M, R, itemsize, K=K)
    np_ = _round_up(N, tnf)
    kpf = _round_up(K, tkf)
    n_k = kpf // tkf
    return (kpf * np_ + n_k * rp * np_ + mp * rp + kpf * mp) * itemsize


def unfused_ffn_hbm_bytes(K: int, M: int, N: int, F: int, R1: int, R2: int,
                          Rg: int, itemsize: int) -> int:
    """HBM bytes of the two-call (three-call when gated) path, fwd + bwd.

    Generous to the unfused side: its backward launches are the per-linear
    FUSED ``btt_backward`` kernels (the best case short of this module),
    and every activation tensor moves exactly once per use.  What remains
    is the traffic whole-block fusion exists to delete: the ``(K, F)``
    hidden state and pre-activations streaming between the up/act/down
    launches in the forward and into the act VJP in the backward.
    """
    from .btt_backward import fused_bwd_hbm_bytes

    k8 = _round_up(K, 8)
    fp = _round_up(F, 128)
    n_pre = 2 if Rg else 1
    gemms_fwd = (_fwd_launch_bytes(K, F, N, R1, itemsize)
                 + _fwd_launch_bytes(K, M, F, R2, itemsize))
    gemms_bwd = (fused_bwd_hbm_bytes(K, F, N, R1, itemsize)
                 + fused_bwd_hbm_bytes(K, M, F, R2, itemsize))
    if Rg:
        gemms_fwd += _fwd_launch_bytes(K, F, N, Rg, itemsize)
        gemms_bwd += fused_bwd_hbm_bytes(K, F, N, Rg, itemsize)
    # act fwd: read the pre-activation(s), write h; act bwd: read gh and
    # the saved pre-activation(s), write the upstream cotangent(s).
    act_fwd = (n_pre + 1) * k8 * fp * itemsize
    act_bwd = (1 + 2 * n_pre) * k8 * fp * itemsize
    return gemms_fwd + act_fwd + gemms_bwd + act_bwd


# ---------------------------------------------------------------------------
# Decode specialization: one token per stream, half-factors pinned.
# ---------------------------------------------------------------------------
#
# Serving runs the megakernel forward-only with K = the number of live
# decode streams.  Two things change vs training: row tiles pad to the
# dtype's true sublane granule (f32 8) instead of the every-dtype 32, and
# the six half-factors — identical across steps — are VMEM-pinned, so
# their HBM fetch amortizes over the whole decode run (``steps`` in the
# byte model).  The kernel body is btt_ffn_pallas's own, so fused-decode
# FFN output is bit-identical to the training forward at equal shapes.


def choose_decode_ffn_tiles(M: int, N: int, F: int, R1: int, R2: int,
                            Rg: int, itemsize: int, *, B: int
                            ) -> tuple[int, int, int, int, int, int, int,
                                       int]:
    """(tk, mp, np, fp, r1p, r2p, rgp, vmem_bytes) for a forward-only
    decode launch of the FFN megakernel: ``tk`` = live streams padded to
    the dtype sublane tile; nothing shrinks (the half-factor residency is
    the floor — callers gate on :func:`decode_ffn_vmem_fits`).

    Same contract as :func:`choose_ffn_tiles`: decode kernel launch,
    ``ops`` dispatch gate and ledger DECODE rows all read these numbers.
    """
    tk = _round_up(B, _decode_sublane(itemsize))
    mp = _round_up(M, 128)
    np_ = _round_up(N, 128)
    fp = _round_up(F, 128)
    r1p = _round_up(R1, 128)
    r2p = _round_up(R2, 128)
    rgp = _round_up(Rg, 128) if Rg else 0
    hf = (r1p * np_ + fp * r1p + r2p * fp + mp * r2p
          + (rgp * np_ + fp * rgp)) * itemsize
    vmem = (tk * np_ * itemsize + tk * mp * itemsize + hf
            + tk * fp * itemsize + tk * fp * 4
            + tk * (r1p + r2p + rgp) * 4)
    return tk, mp, np_, fp, r1p, r2p, rgp, vmem


def decode_ffn_vmem_fits(M: int, N: int, F: int, R1: int, R2: int, Rg: int,
                         itemsize: int, *, B: int,
                         budget: int | None = None) -> bool:
    """THE decode-FFN dispatch predicate (mirrors ``ffn_vmem_fits``)."""
    budget = budget or VMEM_BUDGET
    return choose_decode_ffn_tiles(M, N, F, R1, R2, Rg, itemsize,
                                   B=B)[7] <= budget


def decode_ffn_stage_vmem_bytes(M: int, N: int, F: int, R1: int, R2: int,
                                Rg: int, itemsize: int, *, B: int,
                                fused: bool = True,
                                budget: int | None = None) -> int:
    if not fused or not decode_ffn_vmem_fits(M, N, F, R1, R2, Rg, itemsize,
                                             B=B, budget=budget):
        return 0
    return choose_decode_ffn_tiles(M, N, F, R1, R2, Rg, itemsize, B=B)[7]


def btt_ffn_decode_pallas(x: jax.Array, b1: jax.Array, a1: jax.Array,
                          b2: jax.Array, a2: jax.Array,
                          bg: jax.Array | None = None,
                          ag: jax.Array | None = None, *,
                          act: str = "gelu", f_logical: int | None = None,
                          interpret: bool = False) -> jax.Array:
    """Decode-shape FFN megakernel launch (same body, sublane row tiles)."""
    itemsize = jnp.dtype(x.dtype).itemsize
    tk = _round_up(x.shape[0], _decode_sublane(itemsize))
    return btt_ffn_pallas(x, b1, a1, b2, a2, bg, ag, act=act,
                          f_logical=f_logical, tk=tk, interpret=interpret)


def fused_decode_ffn_hbm_bytes(B: int, M: int, N: int, F: int, R1: int,
                               R2: int, Rg: int, itemsize: int, *,
                               steps: int = 1) -> int:
    """HBM bytes ONE decode step of the FFN megakernel moves: the (tk, N)
    activation row in, the (tk, M) row out, half-factor fetches amortized
    over ``steps`` pinned steps.  The (tk, F) hidden tile moves nothing."""
    tk, mp, np_, fp, r1p, r2p, rgp, _ = choose_decode_ffn_tiles(
        M, N, F, R1, R2, Rg, itemsize, B=B)
    io = (tk * np_ + tk * mp) * itemsize
    hf = _hf_elems(np_, mp, fp, r1p, r2p, rgp) * itemsize
    return io + -(-hf // steps)


def unfused_decode_ffn_hbm_bytes(B: int, M: int, N: int, F: int, R1: int,
                                 R2: int, Rg: int, itemsize: int) -> int:
    """HBM bytes of the two-call decode forward: per-linear launches at the
    training 32-row granule (half-factors re-fetched every step — XLA pins
    nothing across dispatches), the ``(B, F)`` hidden state round-tripping
    HBM between the up/act/down launches."""
    k8 = _round_up(B, 8)
    fp = _round_up(F, 128)
    n_pre = 2 if Rg else 1
    gemms = (_fwd_launch_bytes(B, F, N, R1, itemsize)
             + _fwd_launch_bytes(B, M, F, R2, itemsize))
    if Rg:
        gemms += _fwd_launch_bytes(B, F, N, Rg, itemsize)
    act_io = (n_pre + 1) * k8 * fp * itemsize
    return gemms + act_io
