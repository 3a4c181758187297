"""Pallas TPU kernel: fused BTT backward — the paper's bi-directional BWD
stage (Eqs. (10)/(11)/(16)) as ONE ``pallas_call``.

The forward (``btt_linear.py``) computes ``y = (x @ B^T) @ A^T`` with the
``(TK, r)`` intermediate resident in VMEM.  Its VJP needs five contractions:

    t  = x  @ B^T      (K, r)   recomputed — never saved by the forward
    gt = gy @ A        (K, r)
    gx = gt @ B        (K, N)   paper Eq. (16), the data gradient
    gA = gy^T @ t      (M, r)   paper Eq. (10) (half-factor cotangent)
    gB = gt^T @ x      (r, N)   paper Eq. (11)

Issued as separate XLA GEMMs, the two K-sized intermediates ``t``/``gt``
round-trip HBM four times — exactly the off-chip traffic the paper's
on-chip BWD dataflow eliminates (its Z'_3 stays in BRAM between the MUL2
and MUL3 engines).  This kernel keeps them in VMEM scratch and produces all
three gradients in a single pass over ``x``/``gy``.

Tiling (BlockSpec; grid = (K/TK, N/TN), row-major so N is innermost):

  x block   (TK, TN)     — streamed from HBM, read ONCE
  gy block  (TK, MP)     — one fetch per K row-block (constant across N)
  b block   (RP, TN)     — input half-factor column block
  a block   (MP, RP)     — output half-factor, fully VMEM-resident
  gx block  (TK, TN)     — streamed out, written once
  ga block  (MP, RP) f32 — index map is constant (0, 0): the block is
  gb block  (RP, NP) f32   revisited every grid step, so Pallas keeps it in
                           VMEM for the whole (sequential) grid and flushes
                           to HBM exactly once at the end — the same
                           revisiting-accumulator pattern as the forward
                           kernel's scratch ``t``, now applied to outputs.
  t, gt scratch (TK, RP) f32 — the fused intermediates (paper's Z_2 / Z'_3)

Per grid step (k, n): at ``n == 0`` compute ``gt = gy @ a`` and zero ``t``;
every step accumulate ``t += x @ b^T``, emit ``gx = gt @ b`` for this column
block, and accumulate ``gb[:, n] += gt^T @ x``; on the last N block fold the
completed ``t`` into ``ga += gy^T @ t``.  No K-sized tensor ever leaves
VMEM; the only HBM intermediates of the whole BWD stage are the gradients
themselves.

``ga``/``gb`` accumulate and return in f32 (cast to the core dtype happens
once, at the very end, in ``ops.py``) — the bf16 round-trip the unfused
path used to take between ``t`` and the dependent products does not exist
here.

Shapes whose residency exceeds the VMEM budget (``bwd_vmem_fits``) fall
back to the reference path in ``ops.py``; the memory ledger reports the
same ``choose_bwd_tiles`` working set, so the two cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .btt_linear import DEFAULT_TK, DEFAULT_TN, VMEM_BUDGET, _round_up

__all__ = [
    "btt_backward_pallas",
    "choose_bwd_tiles",
    "bwd_vmem_fits",
    "bwd_stage_vmem_bytes",
    "fused_bwd_hbm_bytes",
    "unfused_bwd_hbm_bytes",
    "bwd_flops",
]


def choose_bwd_tiles(M: int, N: int, R: int, itemsize: int, *,
                     tk: int | None = None, tn: int | None = None,
                     K: int | None = None
                     ) -> tuple[int, int, int, int, int, int]:
    """(tk, tn, mp, rp, np, vmem_bytes) for the fused BWD kernel.

    Single source of truth for the BWD stage's residency: the kernel
    launches with these tiles and ``core.memory_ledger`` reports the same
    ``vmem_bytes`` — ledger and launched tiles cannot drift (the FWD stage
    makes the identical promise through ``btt_linear.choose_tiles``).

    ``K`` caps ``tk`` at the sublane-aligned row count actually present
    (the paper's regime is K=32 — padding it to a 256-row block would 8x
    the streamed traffic and residency).  Lane-aligned ``N`` up to two
    default tiles runs as a single N block (zero column padding on the
    paper's 768-wide layers).  ``tk`` then shrinks until the working set
    fits VMEM_BUDGET; the half-factor blocks (``a``, ``ga``) and the
    full-width ``gb`` accumulator do not scale with ``tk``, so oversized
    layers may never fit — callers gate on :func:`bwd_vmem_fits` and fall
    back to the unfused path.
    """
    tk = tk or DEFAULT_TK
    if K is not None:
        tk = min(tk, _round_up(K, 32))  # 32: every dtype's sublane tile
    if tn is None:
        tn = (_round_up(N, 128) if N <= 2 * DEFAULT_TN else DEFAULT_TN)
    mp = _round_up(M, 128)
    rp = _round_up(R, 128)
    np_ = _round_up(N, tn)

    # gy (tk, mp) + a (mp, rp) + x (tk, tn) + b (rp, tn) + gx (tk, tn)
    # + ga (mp, rp) f32 + gb (rp, np) f32 + t/gt scratch (tk, rp) f32 each
    def vmem(tk_):
        return (tk_ * mp * itemsize + mp * rp * itemsize
                + tk_ * tn * itemsize + rp * tn * itemsize
                + tk_ * tn * itemsize
                + mp * rp * 4 + rp * np_ * 4
                + 2 * tk_ * rp * 4)

    while tk > 64 and vmem(tk) > VMEM_BUDGET:
        tk //= 2
    return tk, tn, mp, rp, np_, vmem(tk)


def bwd_vmem_fits(M: int, N: int, R: int, itemsize: int,
                  K: int | None = None) -> bool:
    """True iff the fused BWD working set fits the kernel VMEM budget."""
    return choose_bwd_tiles(M, N, R, itemsize, K=K)[5] <= VMEM_BUDGET


def bwd_stage_vmem_bytes(M: int, N: int, R: int, itemsize: int,
                         K: int | None = None, *,
                         fused: bool = True) -> int:
    """VMEM working set the BWD stage ACTUALLY launches for this layer:
    the fused kernel's when ``fused`` and it fits the budget (the path
    ``ops.py`` takes), else the operand-swap forward launch's
    (``btt_linear_pallas(gy, A^T, B^T)`` — output dim N, rank R).
    ``fused=False`` mirrors ``fused_bwd=False`` at the op level.
    ``core.memory_ledger`` reports exactly this number, so the ledger and
    the launched tiles cannot drift.
    """
    if fused:
        vm = choose_bwd_tiles(M, N, R, itemsize, K=K)[5]
        if vm <= VMEM_BUDGET:
            return vm
    from .btt_linear import choose_tiles

    return choose_tiles(N, R, itemsize, K=K)[4]


def _bwd_kernel(x_ref, gy_ref, b_ref, a_ref, gx_ref, ga_ref, gb_ref,
                t_ref, gt_ref, *, n_blocks: int, tn: int):
    """Grid (nK, nN); see module docstring for block shapes."""
    k = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when((k == 0) & (n == 0))
    def _zero_accumulators():
        ga_ref[...] = jnp.zeros_like(ga_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    @pl.when(n == 0)
    def _row_start():
        t_ref[...] = jnp.zeros_like(t_ref)
        # gt = gy @ a, once per K row-block (the gy block is constant
        # across the inner N loop).
        gt_ref[...] = jax.lax.dot_general(
            gy_ref[...], a_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # t += x @ b^T  (same MXU GEMM as the forward's stage 1).
    t_ref[...] += jax.lax.dot_general(
        x_ref[...], b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # gx tile: gt @ b — paper Eq. (16) by operand swap, streamed out.
    gx_ref[...] = jax.lax.dot_general(
        gt_ref[...].astype(b_ref.dtype), b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(gx_ref.dtype)

    # gb column block: gt^T @ x, accumulated across the K grid in the
    # VMEM-resident f32 output block (x promoted to f32 — the whole
    # core-gradient chain stays f32 until the final cast in ops.py).
    col = pl.multiple_of(n * tn, tn)
    gb_ref[:, pl.ds(col, tn)] += jax.lax.dot_general(
        gt_ref[...], x_ref[...].astype(jnp.float32),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(n == n_blocks - 1)
    def _fold_ga():
        # t is complete for this K row-block: ga += gy^T @ t.
        ga_ref[...] += jax.lax.dot_general(
            gy_ref[...].astype(jnp.float32), t_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _bwd_kernel_q(s_ref, x_ref, gy_ref, b_ref, a_ref, gx_ref, ga_ref,
                  gb_ref, t_ref, gt_ref, *, n_blocks: int, tn: int):
    """Quantized-operand BWD: x/b/a arrive in storage dtypes with SMEM
    scales ``s = [s_x, s_b, s_a]`` (``gy`` is the compute-dtype cotangent)
    and dequantize tile-by-tile in VMEM.  The gradients are those of the
    DEQUANTIZED operands (straight-through: rounding treated as identity),
    so every product below is against ``s * q`` and the f32 accumulator
    chain of ``_bwd_kernel`` is preserved."""
    k = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when((k == 0) & (n == 0))
    def _zero_accumulators():
        ga_ref[...] = jnp.zeros_like(ga_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    s_x = s_ref[0, 0]
    s_b = s_ref[0, 1]
    b_f = b_ref[...].astype(jnp.float32)
    x_f = x_ref[...].astype(jnp.float32)

    @pl.when(n == 0)
    def _row_start():
        t_ref[...] = jnp.zeros_like(t_ref)
        # gt = gy @ (s_a * a), once per K row-block.
        gt_ref[...] = jax.lax.dot_general(
            gy_ref[...].astype(jnp.float32),
            a_ref[...].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * s_ref[0, 2]

    # t += (s_x x) @ (s_b b)^T — t accumulates the DEQUANTIZED intermediate.
    t_ref[...] += jax.lax.dot_general(
        x_f, b_f,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * (s_x * s_b)

    # gx tile: gt @ (s_b b), streamed out in the compute dtype.
    gx_ref[...] = (jax.lax.dot_general(
        gt_ref[...], b_f,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * s_b).astype(gx_ref.dtype)

    # gb column block: gt^T @ (s_x x), f32-resident accumulator.
    col = pl.multiple_of(n * tn, tn)
    gb_ref[:, pl.ds(col, tn)] += jax.lax.dot_general(
        gt_ref[...], x_f,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * s_x

    @pl.when(n == n_blocks - 1)
    def _fold_ga():
        # t already carries both scales: ga += gy^T @ t unchanged.
        ga_ref[...] += jax.lax.dot_general(
            gy_ref[...].astype(jnp.float32), t_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


@functools.partial(jax.jit,
                   static_argnames=("tk", "tn", "interpret", "out_dtype"))
def btt_backward_pallas(x: jax.Array, gy: jax.Array, b: jax.Array,
                        a: jax.Array, *, scales: jax.Array | None = None,
                        out_dtype=None, tk: int | None = None,
                        tn: int | None = None, interpret: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused BWD stage: ``(gx (K, N), ga (M, R) f32, gb (R, N) f32)``.

    ``x (K, N)`` is the saved layer input, ``gy (K, M)`` the output
    cotangent, ``b (R, N)`` / ``a (M, R)`` the rebuilt half-factors.  All
    dims are padded to hardware tiles; zero padding is exact for every
    contraction here (padded rows/cols of x, gy, a, b are zero, so they
    contribute nothing to any product).  ``interpret=True`` runs the kernel
    body in Python on CPU — the validation path, as for every kernel in
    this package.

    ``scales`` ((1, 3) f32 ``[s_x, s_b, s_a]``) switches to the
    quantized-operand kernel (``_bwd_kernel_q``): x/b/a stream in storage
    dtypes, dequantize in VMEM, and the returned gradients are w.r.t. the
    dequantized operands; ``out_dtype`` names ``gx``'s compute dtype.
    """
    K, N = x.shape
    _, M = gy.shape
    R, _ = b.shape
    out_dtype = out_dtype or x.dtype

    itemsize = max(jnp.dtype(v.dtype).itemsize for v in (x, gy, b, a))
    tk, tn, mp, rp, np_, _ = choose_bwd_tiles(M, N, R, itemsize, tk=tk,
                                              tn=tn, K=K)

    kp = _round_up(K, tk)
    xp = jnp.pad(x, ((0, kp - K), (0, np_ - N)))
    gyp = jnp.pad(gy, ((0, kp - K), (0, mp - M)))
    bp = jnp.pad(b, ((0, rp - R), (0, np_ - N)))
    ap = jnp.pad(a, ((0, mp - M), (0, rp - R)))

    n_blocks = np_ // tn
    grid = (kp // tk, n_blocks)

    data_specs = [
        pl.BlockSpec((tk, tn), lambda k, n: (k, n)),    # x
        pl.BlockSpec((tk, mp), lambda k, n: (k, 0)),    # gy
        pl.BlockSpec((rp, tn), lambda k, n: (0, n)),    # b
        pl.BlockSpec((mp, rp), lambda k, n: (0, 0)),    # a (resident)
    ]
    if scales is None:
        kern = functools.partial(_bwd_kernel, n_blocks=n_blocks, tn=tn)
        in_specs, operands = data_specs, (xp, gyp, bp, ap)
    else:
        kern = functools.partial(_bwd_kernel_q, n_blocks=n_blocks, tn=tn)
        in_specs = [pl.BlockSpec((1, 3), lambda k, n: (0, 0),
                                 memory_space=pltpu.SMEM)] + data_specs
        operands = (scales.astype(jnp.float32).reshape(1, 3),
                    xp, gyp, bp, ap)

    gx, ga, gb = pl.pallas_call(
        kern,
        name="btt_backward",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((tk, tn), lambda k, n: (k, n)),    # gx
            pl.BlockSpec((mp, rp), lambda k, n: (0, 0)),    # ga (accumulator)
            pl.BlockSpec((rp, np_), lambda k, n: (0, 0)),   # gb (accumulator)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, np_), out_dtype),
            jax.ShapeDtypeStruct((mp, rp), jnp.float32),
            jax.ShapeDtypeStruct((rp, np_), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tk, rp), jnp.float32),   # t
            pltpu.VMEM((tk, rp), jnp.float32),   # gt
        ],
        # Both grid axes carry accumulation state (ga/gb revisit across k,
        # t across n) — neither may be parallelized.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return gx[:K, :N], ga[:M, :R], gb[:R, :N]


# ---------------------------------------------------------------------------
# Analytic HBM-traffic / FLOP models (shared by benchmarks and tests).
# ---------------------------------------------------------------------------


def bwd_flops(K: int, M: int, N: int, R: int) -> int:
    """MACs x2 of the five BWD contractions (t, gt, gx, ga, gb)."""
    return 2 * K * R * (2 * M + 3 * N)


def fused_bwd_hbm_bytes(K: int, M: int, N: int, R: int, itemsize: int) -> int:
    """HBM bytes moved by ONE fused-kernel BWD launch (tile-derived).

    Reads: x once, gy once per K row-block (its block index is constant
    across the inner N loop, so one fetch per row = K*M total), b once per
    K row-block, a once (its block index never changes).  Writes: gx, plus
    the single end-of-grid flush of the f32 ga/gb accumulators.  No K-sized
    intermediate appears on either side.  All counts are over the launch's
    padded dims — padded bytes are real bytes on the wire.
    """
    tk, tn, mp, rp, np_, _ = choose_bwd_tiles(M, N, R, itemsize, K=K)
    kp = _round_up(K, tk)
    n_k = kp // tk
    reads = (kp * np_ + kp * mp + n_k * rp * np_ + mp * rp) * itemsize
    writes = kp * np_ * itemsize + (mp * rp + rp * np_) * 4
    return reads + writes


def unfused_bwd_hbm_bytes(K: int, M: int, N: int, R: int,
                          itemsize: int) -> int:
    """HBM bytes moved by the unfused BWD path: four XLA GEMMs for the core
    gradients (the K-sized t/gt round-trip HBM in f32) + the operand-swap
    forward-kernel launch for gx.

    The GEMM operands/results are counted at their (8, 128)-tile-padded
    HBM footprint (how XLA stores TPU arrays), each read/written ONCE per
    GEMM — generous to XLA (perfect in-GEMM fusion, no re-reads).  The gx
    launch uses the forward kernel's own tile chooser, so the comparison
    is tile-for-tile fair with the fused model.
    """
    from .btt_linear import choose_tiles

    k8 = _round_up(K, 8)
    mp = _round_up(M, 128)
    rp = _round_up(R, 128)
    np_ = _round_up(N, 128)
    # t = x @ b^T; gt = gy @ a; ga = gy^T @ t; gb = gt^T @ x   (t/gt in f32)
    gemms = (
        (k8 * np_ + rp * np_) * itemsize + k8 * rp * 4       # t
        + (k8 * mp + mp * rp) * itemsize + k8 * rp * 4       # gt
        + k8 * mp * itemsize + k8 * rp * 4 + mp * rp * 4     # ga
        + k8 * rp * 4 + k8 * np_ * itemsize + rp * np_ * 4   # gb
    )
    # gx via btt_linear_pallas(gy, a^T, b^T): x:=gy streamed once, the
    # "b" operand (a^T, shape (R, M)) refetched per K row-block, the
    # resident "a" operand (b^T, (N, R)) fetched once, y:=gx written once.
    tkf = choose_tiles(N, R, itemsize, K=K)[0]
    kpf = _round_up(K, tkf)
    n_k = kpf // tkf
    gx_launch = (kpf * mp + n_k * rp * mp + np_ * rp + kpf * np_) * itemsize
    return gemms + gx_launch
