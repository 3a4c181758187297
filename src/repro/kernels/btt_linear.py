"""Pallas TPU kernel: fused bidirectional-TT (BTT) linear forward.

The paper's BTT contraction reduces a TT linear layer to
``y = A @ (B @ x)`` with tiny half-factors ``A (M, r)`` / ``B (r, N)``
(Sec. IV-B).  On FPGA the intermediate ``Z_2 = B @ x`` lives in on-chip
BRAM between the MUL1 and MUL2 engines.  The TPU analogue implemented here:
one ``pallas_call`` computes both GEMMs per output tile with the ``(TK, r)``
intermediate held in a **VMEM scratch accumulator** — it never round-trips
through HBM, exactly the paper's on-chip-only dataflow.

Tiling (BlockSpec):
  grid = (K / TK, N / TN); iteration is row-major so the N axis is innermost.
  x block  (TK, TN)   — streamed from HBM
  b block  (R,  TN)   — input half-factor, R = padded rank (lane-aligned)
  a block  (M,  R)    — output half-factor, fully VMEM-resident (it is tiny:
                        M·r ≤ a few MB — this residency is the kernel-level
                        expression of the paper's "all parameters on chip")
  y block  (TK, M)    — written once per K row-block
  t scratch (TK, R) f32 — the fused intermediate (paper's Z_2)

Per grid step: ``t += x_blk @ b_blk^T`` (MXU GEMM 1); on the last N block,
``y = t @ a^T`` (MXU GEMM 2).  Both contractions hit the MXU with
hardware-aligned shapes; this is the "few large matmuls, not 2d skinny ones"
adaptation recorded in DESIGN.md.

The same kernel computes the backward data gradient by operand swap:
``gx = (gy @ A) @ B = btt(gy, b=A^T, a=B^T)`` — see ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cost_model import sublane as _sublane

__all__ = ["btt_linear_pallas", "choose_tiles", "DEFAULT_TK", "DEFAULT_TN",
           "btt_linear_decode_pallas", "choose_decode_tiles",
           "decode_linear_vmem_fits", "decode_linear_stage_vmem_bytes",
           "fused_decode_linear_hbm_bytes", "unfused_decode_linear_hbm_bytes"]

DEFAULT_TK = 256
DEFAULT_TN = 512
VMEM_BUDGET = 12 * 1024 * 1024


def choose_tiles(M: int, R: int, itemsize: int, *, tk: int | None = None,
                 tn: int | None = None,
                 K: int | None = None) -> tuple[int, int, int, int, int]:
    """(tk, tn, mp, rp, vmem_bytes): tile sizes + padded dims + the per-grid-
    step VMEM working set, shrinking ``tk`` until it fits VMEM_BUDGET.

    ``K`` (the paper's batch x seq, tiny in the on-FPGA regime: 32) caps
    ``tk`` at the sublane-aligned row count actually present, so a K=32
    launch doesn't pad to — and stream — a 256-row block (8x the real
    traffic and residency).

    Single source of truth for the kernel's residency: ``btt_linear_pallas``
    launches with these tiles and ``core.memory_ledger`` reports the same
    ``vmem_bytes`` — the two cannot drift.
    """
    tk = tk or DEFAULT_TK
    tn = tn or DEFAULT_TN
    if K is not None:
        # 32-row alignment satisfies every dtype's sublane tile (f32 8,
        # bf16 16, int8 32).
        tk = min(tk, _round_up(K, 32))
    mp = _round_up(M, 128)
    rp = _round_up(R, 128)

    # y block (tk, mp) + a (mp, rp) + x (tk, tn) + b (rp, tn) + t (tk, rp) f32
    def vmem(tk_):
        return (tk_ * mp * itemsize + mp * rp * itemsize + tk_ * tn * itemsize
                + rp * tn * itemsize + tk_ * rp * 4)

    while tk > 64 and vmem(tk) > VMEM_BUDGET:
        tk //= 2
    return tk, tn, mp, rp, vmem(tk)


def _fwd_kernel(x_ref, b_ref, a_ref, y_ref, t_ref, *, n_blocks: int):
    """Grid (nK, nN); see module docstring for block shapes."""
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _zero():
        t_ref[...] = jnp.zeros_like(t_ref)

    # GEMM 1: accumulate the fused intermediate t = x @ b^T in f32.
    t_ref[...] += jax.lax.dot_general(
        x_ref[...], b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(n == n_blocks - 1)
    def _emit():
        # GEMM 2: y = t @ a^T, emitted once per K row-block.
        y_ref[...] = jax.lax.dot_general(
            t_ref[...].astype(a_ref.dtype), a_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(y_ref.dtype)


def _fwd_kernel_q(s_ref, x_ref, b_ref, a_ref, y_ref, t_ref, *,
                  n_blocks: int):
    """Quantized-operand forward: identical dataflow to ``_fwd_kernel``
    but x/b/a arrive in their storage dtypes (int8 / fp8 / anything) with
    per-tensor scales ``s = [s_x, s_b, s_a]`` in SMEM; tiles dequantize to
    f32 *in VMEM* before each MXU dot — the low-precision tensors never
    exist densely in f32 in HBM, and the accumulator chain stays f32
    (fp8 dots are thereby emulated on backends without native fp8 MXU
    support)."""
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _zero():
        t_ref[...] = jnp.zeros_like(t_ref)

    t_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * (s_ref[0, 0] * s_ref[0, 1])

    @pl.when(n == n_blocks - 1)
    def _emit():
        a = a_ref[...].astype(jnp.float32) * s_ref[0, 2]
        y_ref[...] = jax.lax.dot_general(
            t_ref[...], a,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(y_ref.dtype)


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit,
                   static_argnames=("tk", "tn", "interpret", "out_dtype"))
def btt_linear_pallas(x: jax.Array, b: jax.Array, a: jax.Array, *,
                      scales: jax.Array | None = None, out_dtype=None,
                      tk: int | None = None, tn: int | None = None,
                      interpret: bool = False) -> jax.Array:
    """``y (K, M) = (x (K, N) @ b(R, N)^T) @ a(M, R)^T`` via one fused kernel.

    Pads every dim to hardware tiles (K, N to the block sizes; R, M to 128
    lanes); zero padding is exact for this bilinear map.  ``interpret=True``
    runs the kernel body in Python on CPU (used for all validation here —
    TPU v5e is the *target*).

    ``scales`` (a (1, 3) f32 array ``[s_x, s_b, s_a]``) switches to the
    quantized-operand kernel: x/b/a stream in their storage dtypes and
    dequantize tile-by-tile in VMEM (``_fwd_kernel_q``); ``out_dtype``
    then names the compute dtype of ``y`` (default ``x.dtype`` — wrong for
    int8 inputs, so quantized callers pass it).
    """
    K, N = x.shape
    R, _ = b.shape
    M, _ = a.shape
    out_dtype = out_dtype or x.dtype

    # --- choose tiles under a VMEM budget -------------------------------
    itemsize = max(jnp.dtype(v.dtype).itemsize for v in (x, b, a))
    tk, tn, mp, rp, _ = choose_tiles(M, R, itemsize, tk=tk, tn=tn, K=K)

    kp = _round_up(K, tk)
    np_ = _round_up(N, tn)
    xp = jnp.pad(x, ((0, kp - K), (0, np_ - N)))
    bp = jnp.pad(b, ((0, rp - R), (0, np_ - N)))
    ap = jnp.pad(a, ((0, mp - M), (0, rp - R)))

    n_blocks = np_ // tn
    grid = (kp // tk, n_blocks)

    data_specs = [
        pl.BlockSpec((tk, tn), lambda k, n: (k, n)),   # x
        pl.BlockSpec((rp, tn), lambda k, n: (0, n)),   # b
        pl.BlockSpec((mp, rp), lambda k, n: (0, 0)),   # a (resident)
    ]
    if scales is None:
        kern = functools.partial(_fwd_kernel, n_blocks=n_blocks)
        in_specs, operands = data_specs, (xp, bp, ap)
    else:
        kern = functools.partial(_fwd_kernel_q, n_blocks=n_blocks)
        in_specs = [pl.BlockSpec((1, 3), lambda k, n: (0, 0),
                                 memory_space=pltpu.SMEM)] + data_specs
        operands = (scales.astype(jnp.float32).reshape(1, 3), xp, bp, ap)

    y = pl.pallas_call(
        kern,
        name="btt_linear",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tk, mp), lambda k, n: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, mp), out_dtype),
        scratch_shapes=[pltpu.VMEM((tk, rp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return y[:K, :M]


# ---------------------------------------------------------------------------
# Decode specialization: one token per stream, half-factors pinned.
# ---------------------------------------------------------------------------
#
# At decode time K is the number of concurrent streams (1-16 in the serving
# regime), not batch x seq — the training chooser's 32-row granule would pad
# a batch-1 stream to 32 streamed rows.  The decode chooser pads only to the
# dtype's true sublane tile (f32 8 / bf16 16 / int8 32) and, because the
# half-factors don't change between steps, treats them as VMEM-PINNED: the
# analytic byte model amortizes their fetch over ``steps`` decode steps,
# which is what the serve loop's jitted step achieves by re-passing the same
# device-resident arrays.


def choose_decode_tiles(M: int, R: int, itemsize: int, *, B: int,
                        tn: int | None = None
                        ) -> tuple[int, int, int, int, int]:
    """(tk, tn, mp, rp, vmem_bytes) for a decode-shape launch: ``tk`` is the
    stream count padded to the dtype sublane tile (TK=1-row tiles, hardware
    granule permitting) and ``tn`` shrinks to fit instead.

    Same single-source-of-truth contract as :func:`choose_tiles`: the decode
    kernel launches with these tiles, ``ops`` gates on
    :func:`decode_linear_vmem_fits`, and the ledger's DECODE rows report the
    same ``vmem_bytes``.
    """
    tk = _round_up(B, _sublane(itemsize))
    tn = tn or DEFAULT_TN
    mp = _round_up(M, 128)
    rp = _round_up(R, 128)

    def vmem(tn_):
        return (tk * mp * itemsize + mp * rp * itemsize + tk * tn_ * itemsize
                + rp * tn_ * itemsize + tk * rp * 4)

    while tn > 128 and vmem(tn) > VMEM_BUDGET:
        tn //= 2
    return tk, tn, mp, rp, vmem(tn)


def decode_linear_vmem_fits(M: int, R: int, itemsize: int, *, B: int,
                            budget: int | None = None) -> bool:
    budget = budget or VMEM_BUDGET
    return choose_decode_tiles(M, R, itemsize, B=B)[4] <= budget


def decode_linear_stage_vmem_bytes(M: int, R: int, itemsize: int, *, B: int,
                                   fused: bool = True,
                                   budget: int | None = None) -> int:
    """VMEM working set a decode TT-linear launch holds (0 when unfused or
    over budget — the fallback two-call path keeps no scratch)."""
    if not fused or not decode_linear_vmem_fits(M, R, itemsize, B=B,
                                                budget=budget):
        return 0
    return choose_decode_tiles(M, R, itemsize, B=B)[4]


@functools.partial(jax.jit, static_argnames=("interpret",))
def btt_linear_decode_pallas(x: jax.Array, b: jax.Array, a: jax.Array, *,
                             interpret: bool = False) -> jax.Array:
    """Decode-shape ``btt_linear_pallas``: same fused dataflow, row tiles at
    the dtype sublane granule so a handful of streams doesn't pad to a
    training-size 32-row block."""
    K = x.shape[0]
    R = b.shape[0]
    M = a.shape[0]
    itemsize = jnp.dtype(x.dtype).itemsize
    tk, tn, _, _, _ = choose_decode_tiles(M, R, itemsize, B=K)
    return btt_linear_pallas(x, b, a, tk=tk, tn=tn, interpret=interpret)


def fused_decode_linear_hbm_bytes(B: int, M: int, N: int, R: int,
                                  itemsize: int, *, steps: int = 1) -> int:
    """HBM bytes ONE decode step of the fused TT linear moves, half-factor
    fetches amortized over ``steps`` pinned decode steps.  Per step only the
    (tk, N) activation row goes in and the (tk, M) row comes out; the
    intermediate lives in VMEM scratch."""
    tk, tn, mp, rp, _ = choose_decode_tiles(M, R, itemsize, B=B)
    np_ = _round_up(N, tn)
    io = tk * np_ * itemsize + tk * mp * itemsize
    factors = (rp * np_ + mp * rp) * itemsize
    return io + -(-factors // steps)


def unfused_decode_linear_hbm_bytes(B: int, M: int, N: int, R: int,
                                    itemsize: int) -> int:
    """HBM bytes of the unfused two-GEMM decode path: training-granule
    (32-row) launch padding, the ``(K, R)`` intermediate round-tripping HBM
    between the GEMMs, half-factors re-fetched every step (XLA pins nothing
    across dispatches)."""
    kp = _round_up(B, 32)
    rp = _round_up(R, 128)
    mp = _round_up(M, 128)
    np_ = _round_up(N, 128)
    g1 = kp * np_ * itemsize + rp * np_ * itemsize + kp * rp * itemsize
    g2 = kp * rp * itemsize + mp * rp * itemsize + kp * mp * itemsize
    return g1 + g2
