"""jit'd public wrappers around the Pallas kernels, with pure-JAX fallbacks.

* ``btt_linear_op(cores, x, spec)`` — the paper's BTT linear executed by the
  fused Pallas forward (``btt_linear.py``) under a custom VJP that implements
  the paper's fused backward (Sec. V-B2): no K-sized intermediate is saved.
  The half-factors ``(A, B)`` are built from the cores ONCE per invocation
  (``tt_half_factors``) and the custom VJP lives at the half-factor level
  (``_hf_linear``): the bwd reuses the saved (tiny, K-independent) factors
  and plain autodiff chains their cotangents back into per-core gradients —
  no rebuild in either the fwd or the bwd.  With ``fused_bwd=True``
  (default) the whole BWD stage — data gradient AND half-factor gradients —
  runs as ONE Pallas kernel (``btt_backward.py``) with the recomputed
  ``t``/``gt`` intermediates resident in VMEM scratch; shapes whose working
  set exceeds the VMEM budget, or ``fused_bwd=False``, take the reference
  path: ``gx`` through the forward kernel by operand swap
  (``gx = btt(gy, A^T, B^T)``) plus four XLA GEMMs for the core gradients
  (f32 end to end).

* ``btt_ffn_op(up_cores, down_cores, gate_cores, x, ...)`` — the WHOLE FFN
  block (both TT linears + activation; three linears when gated) as one
  fused Pallas forward and one fused Pallas backward (``btt_ffn.py``): the
  ``(K, d_ff)`` hidden state lives only in VMEM scratch, and the backward
  recomputes it from ``x``, so the block's training residual is just the
  layer input.  Shapes whose working set exceeds the VMEM budget
  (``ffn_vmem_fits`` — the ledger gates on the same predicate), or
  ``fused_ffn=False``, take the two-call path through ``_hf_linear``.

* ``ttm_embed_op(cores, ids, spec)`` — gather-free TTM lookup via the d=3
  one-hot kernel; falls back to the jnp gather chain when d != 3 or the cores
  exceed the VMEM residency budget.  A custom VJP routes the core gradients
  through autodiff of the pure-jnp one-hot chain (``ref.ttm_embed_ref``) —
  the same math as the gather-chain oracle, so the kernel path is
  trainable.

* ``flash_mha_op(q, k, v)`` — training/prefill attention as the fused flash
  kernels: forward saves only ``(O, m, l)`` per layer; the backward is ONE
  ``pallas_call`` (``flash_backward.py``) recomputing probability tiles in
  VMEM — no S×S tensor is ever saved or moved.  Where the whole sequence
  is one tile, both launches take head blocks of whole sequences.  Shapes
  whose backward working set exceeds the VMEM budget (dK/dV residency
  grows with S) fall back to the pure-JAX ``blockwise_attention`` under
  plain autodiff.

Kernel selection: on a TPU backend the compiled kernel runs natively; on CPU
(this container) ``interpret=True`` executes the kernel body in Python — the
correctness path used by every test.  ``use_kernel=False`` forces the pure
JAX path (what the production dry-run lowers, keeping HLO analyzable).

Precision: every trainable op takes ``precision`` (a ``PrecisionConfig``).
With a scaled format the custom-VJP *boundary* quantizes the at-rest set —
half-factors at ``param_dtype``, the saved layer input / flash residuals at
``act_dtype`` — per-tensor max-abs RTN, and saves the quantized arrays plus
an f32 scale stack as the residuals.  The fused kernels dequantize those
tiles in VMEM (``scales=`` operand) and keep f32 accumulator chains; no
dense low-precision tensor round-trips HBM between FWD and BWD.  Gradients
follow the straight-through estimator: cotangents are w.r.t. the
*dequantized* operands.  Cast-only ``bfloat16`` rides the same path with
unit scales.  ``precision=None`` (or all-f32) is byte-identical to the
pre-precision kernels.
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant as _quant
from repro.core.contraction import tt_forward_btt, ttm_lookup, token_digits
from repro.core.tt import TTMSpec, TTSpec, tt_half_factors
from repro.tracing import FLASH_ROWS

from .btt_backward import btt_backward_pallas, bwd_vmem_fits
from .btt_ffn import (
    ACTS as _FFN_ACTS,
    btt_ffn_bwd_pallas,
    btt_ffn_decode_pallas,
    btt_ffn_pallas,
    decode_ffn_vmem_fits,
    ffn_vmem_fits,
)
from .btt_linear import (
    btt_linear_decode_pallas,
    btt_linear_pallas,
    decode_linear_vmem_fits,
)
from .flash_attention import flash_attention_pallas
from .flash_backward import (
    attn_bwd_vmem_fits,
    choose_attn_tiles,
    flash_attention_bwd_pallas,
)
from .flash_decode import (
    decode_attn_vmem_fits,
    flash_decode_pallas,
    paged_decode_ref,
)
from .ttm_embed import ttm_embed_pallas

__all__ = ["btt_linear_op", "btt_ffn_op", "ttm_embed_op", "flash_mha_op",
           "flash_decode_op", "btt_linear_decode_op", "btt_ffn_decode_op",
           "kernel_interpret_default"]

_VMEM_CORE_BUDGET = 8 * 1024 * 1024  # resident-core budget for ttm kernel


def kernel_interpret_default() -> bool:
    """interpret=True everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Precision plumbing (see module docstring).  The VJP boundary stores each
# operand in its at-rest format; scaled formats carry one f32 scale, cast-only
# formats a unit scale — the quant kernels' ``tile.astype(f32) * scale``
# dequant handles both uniformly.
# ---------------------------------------------------------------------------


def _prep(v: jax.Array, fmt: str) -> tuple[jax.Array, jax.Array]:
    """``v -> (stored, scale)`` in the at-rest format ``fmt``."""
    if fmt == "float32":
        return v, jnp.float32(1.0)
    f = _quant.resolve(fmt)
    if not f.needs_scale:
        return v.astype(f.dtype), jnp.float32(1.0)
    return _quant.quantize(v, fmt)


def _deq(v: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (v.astype(jnp.float32) * scale).astype(dtype)


def _precision_fmts(precision, x_dtype) -> tuple[str, str]:
    """``(param_fmt, act_fmt)`` strings from a ``PrecisionConfig`` (or None).

    A format equal to the op's compute dtype is storage-identity (the
    residual already lives in that dtype), so it normalizes to the
    ``"float32"`` sentinel — which ``_prep`` treats as "store as-is, unit
    scale" — keeping such configs on the legacy bit-identical path.
    """
    if precision is None:
        return "float32", "float32"
    name = jnp.dtype(x_dtype).name
    pfmt = precision.param_dtype
    afmt = precision.resolved_act(name)
    if pfmt == name:
        pfmt = "float32"
    if afmt == name:
        afmt = "float32"
    return pfmt, afmt


# ---------------------------------------------------------------------------
# BTT linear (kernel-backed, fused custom VJP at the half-factor level).
#
# The half-factor build is OUTSIDE the custom VJP: ``btt_linear_op`` (and
# ``btt_ffn_op``) call ``tt_half_factors`` exactly once per invocation and
# plain autodiff chains the (tiny, K-independent) build — the fwd/bwd pair
# below never rebuilds the factors from cores.
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _hf_linear(a: jax.Array, b: jax.Array, x: jax.Array,
               interpret: bool, fused_bwd: bool,
               shard_dims: int = 1, pfmt: str = "float32",
               afmt: str = "float32") -> jax.Array:
    return _hf_linear_impl(a, b, x, interpret, pfmt, afmt)[0]


def _hf_linear_impl(a, b, x, interpret, pfmt, afmt):
    if pfmt == "float32" and afmt == "float32":
        y = btt_linear_pallas(x, b, a, interpret=interpret)
        # Residuals: the layer input and the already-built half-factors
        # (O(r) extra state, K-independent) — no K-sized intermediate, no
        # rebuild.
        return y, (a, b, x, None)
    # Quantized-at-rest: the residual SET is the stored set — half-factors
    # at param_dtype, the layer input at act_dtype, plus the (1, 3) f32
    # scale stack [s_x, s_b, s_a].  The forward consumes the same stored
    # tiles (dequantized in VMEM), so fwd and bwd see identical operands
    # and the STE gradients are exact for the quantized model.
    cdt = x.dtype
    aq, sa = _prep(a, pfmt)
    bq, sb = _prep(b, pfmt)
    xq, sx = _prep(x, afmt)
    scales = jnp.stack([sx, sb, sa]).reshape(1, 3)
    y = btt_linear_pallas(xq, bq, aq, scales=scales, out_dtype=cdt,
                          interpret=interpret)
    return y, (aq, bq, xq, scales)


def _hf_linear_fwd(a, b, x, interpret, fused_bwd, shard_dims, pfmt, afmt):
    return _hf_linear_impl(a, b, x, interpret, pfmt, afmt)


def _hf_linear_bwd(interpret, fused_bwd, shard_dims, pfmt, afmt,
                   residuals, gy):
    a, b, x, scales = residuals
    M, R = a.shape
    N = b.shape[1]
    itemsize = max(jnp.dtype(v.dtype).itemsize for v in (x, gy, b, a))
    k_local = -(-x.shape[0] // max(shard_dims, 1))
    if fused_bwd and bwd_vmem_fits(M, N, R, itemsize, K=k_local):
        # ONE kernel launch: gx streamed, ga/gb accumulated on chip —
        # t/gt never leave VMEM (paper Eqs. (10)/(11)/(16) as one stage).
        # With scales the kernel dequantizes the stored tiles in VMEM and
        # returns STE gradients w.r.t. the dequantized operands.
        gx, ga, gb = btt_backward_pallas(
            x, gy, b, a, scales=scales,
            out_dtype=None if scales is None else gy.dtype,
            interpret=interpret)
    else:
        if scales is not None:
            # Fallback dequantizes once at entry (transient f32 copies);
            # at-rest storage between FWD and BWD stays quantized.
            s = scales.reshape(3)
            x = _deq(x, s[0], gy.dtype)
            b = _deq(b, s[1], gy.dtype)
            a = _deq(a, s[2], gy.dtype)
        # Reference path: data gradient through the fused FORWARD kernel by
        # operand swap (gx = (gy @ A) @ B = btt(gy; b=A^T, a=B^T)); core
        # gradients as four XLA GEMMs with t/gt kept f32 through the
        # dependent products (same math as btt_backward_ref, minus its
        # kernel-idiom gx GEMM, which the operand-swap launch replaces).
        gx = btt_linear_pallas(gy, a.T, b.T, interpret=interpret)
        t = jnp.dot(x, b.T, preferred_element_type=jnp.float32)
        gt = jnp.dot(gy, a, preferred_element_type=jnp.float32)
        ga = jnp.dot(gy.T.astype(jnp.float32), t,
                     preferred_element_type=jnp.float32)
        gb = jnp.dot(gt.T, x.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    if scales is None:
        return ga.astype(a.dtype), gb.astype(b.dtype), gx
    return (ga.astype(gy.dtype), gb.astype(gy.dtype), gx.astype(gy.dtype))


_hf_linear.defvjp(_hf_linear_fwd, _hf_linear_bwd)


def _resolve_shard_dims(shard_dims: int | None) -> int:
    """The row-shard divisor for VMEM dispatch predicates.

    ``None`` means "ask the mesh context": ``meshctx.row_shards()`` — 1
    with no mesh and 1 inside shard_map bodies (local shapes already), the
    GSPMD row-shard product otherwise.  Predicates then gate on the
    *per-device* row count, so fused dispatch survives sharding and stays
    in lockstep with ``core.memory_ledger``'s per-shard rows.
    """
    if shard_dims is not None:
        return max(int(shard_dims), 1)
    from repro.core.meshctx import row_shards

    return row_shards()


def btt_linear_op(cores, x: jax.Array, spec: TTSpec, *,
                  use_kernel: bool = True,
                  interpret: bool | None = None,
                  fused_bwd: bool = True,
                  shard_dims: int | None = None,
                  precision=None) -> jax.Array:
    """``x (K, N) -> y (K, M)`` with W in TT format, BTT contraction.

    ``fused_bwd`` selects the single-kernel BWD stage for the gradients
    (falls back automatically when the shape's working set exceeds the
    kernel VMEM budget); ``False`` forces the operand-swap + XLA-GEMM
    reference path.  ``shard_dims`` (default: mesh-resolved) divides K for
    that VMEM gate only — see ``_resolve_shard_dims``.  ``precision``
    (a ``PrecisionConfig``) selects the at-rest storage formats — see the
    module docstring.
    """
    if not use_kernel:
        return tt_forward_btt(cores, x, spec)
    if interpret is None:
        interpret = kernel_interpret_default()
    pfmt, afmt = _precision_fmts(precision, x.dtype)
    a, b = tt_half_factors(list(cores), spec)  # built once; autodiff chains
    return _hf_linear(a, b, x, interpret, fused_bwd,
                      _resolve_shard_dims(shard_dims), pfmt, afmt)


# ---------------------------------------------------------------------------
# Fused tensorized FFN (whole block: both/all TT linears + activation).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _ffn_fused(a1, b1, a2, b2, ag, bg, x, act: str, f_logical: int,
               interpret: bool, pfmt: str = "float32",
               afmt: str = "float32") -> jax.Array:
    return _ffn_fused_impl(a1, b1, a2, b2, ag, bg, x, act, f_logical,
                           interpret, pfmt, afmt)[0]


def _ffn_fused_impl(a1, b1, a2, b2, ag, bg, x, act, f_logical, interpret,
                    pfmt, afmt):
    if pfmt == "float32" and afmt == "float32":
        y = btt_ffn_pallas(x, b1, a1, b2, a2, bg, ag, act=act,
                           f_logical=f_logical, interpret=interpret)
        # The block's whole residual set: x and the half-factors.  The
        # hidden state and the activation pre-images are recomputed in
        # VMEM by the backward — FFN residuals are O(K*d_model), never
        # O(K*d_ff).
        return y, (a1, b1, a2, b2, ag, bg, x, None)
    # Quantized-at-rest residual set + the (1, 8) scale stack
    # [s_x, s_b1, s_a1, s_bg, s_ag, s_b2, s_a2, pad] (gate slots zero when
    # ungated — the kernel never reads them then).
    cdt = x.dtype
    xq, sx = _prep(x, afmt)
    b1q, sb1 = _prep(b1, pfmt)
    a1q, sa1 = _prep(a1, pfmt)
    b2q, sb2 = _prep(b2, pfmt)
    a2q, sa2 = _prep(a2, pfmt)
    zero = jnp.float32(0.0)
    if bg is not None:
        bgq, sbg = _prep(bg, pfmt)
        agq, sag = _prep(ag, pfmt)
    else:
        bgq = agq = None
        sbg = sag = zero
    scales = jnp.stack([sx, sb1, sa1, sbg, sag, sb2, sa2,
                        zero]).reshape(1, 8)
    y = btt_ffn_pallas(xq, b1q, a1q, b2q, a2q, bgq, agq, act=act,
                       f_logical=f_logical, scales=scales, out_dtype=cdt,
                       interpret=interpret)
    return y, (a1q, b1q, a2q, b2q, agq, bgq, xq, scales)


def _ffn_fused_fwd(a1, b1, a2, b2, ag, bg, x, act, f_logical, interpret,
                   pfmt, afmt):
    return _ffn_fused_impl(a1, b1, a2, b2, ag, bg, x, act, f_logical,
                           interpret, pfmt, afmt)


def _ffn_fused_bwd(act, f_logical, interpret, pfmt, afmt, residuals, gy):
    a1, b1, a2, b2, ag, bg, x, scales = residuals
    grads = btt_ffn_bwd_pallas(x, gy, b1, a1, b2, a2, bg, ag, act=act,
                               f_logical=f_logical, scales=scales,
                               out_dtype=None if scales is None else gy.dtype,
                               interpret=interpret)
    gdt = gy.dtype
    if bg is not None:
        gx, ga1, gb1, ga2, gb2, gag, gbg = grads
        if scales is not None:
            return (ga1.astype(gdt), gb1.astype(gdt), ga2.astype(gdt),
                    gb2.astype(gdt), gag.astype(gdt), gbg.astype(gdt), gx)
        return (ga1.astype(a1.dtype), gb1.astype(b1.dtype),
                ga2.astype(a2.dtype), gb2.astype(b2.dtype),
                gag.astype(ag.dtype), gbg.astype(bg.dtype), gx)
    gx, ga1, gb1, ga2, gb2 = grads
    if scales is not None:
        return (ga1.astype(gdt), gb1.astype(gdt), ga2.astype(gdt),
                gb2.astype(gdt), None, None, gx)
    return (ga1.astype(a1.dtype), gb1.astype(b1.dtype),
            ga2.astype(a2.dtype), gb2.astype(b2.dtype), None, None, gx)


_ffn_fused.defvjp(_ffn_fused_fwd, _ffn_fused_bwd)


def btt_ffn_op(up_cores, down_cores, gate_cores, x: jax.Array,
               up_spec: TTSpec, down_spec: TTSpec,
               gate_spec: TTSpec | None = None, *, act: str = "gelu",
               f_logical: int | None = None,
               interpret: bool | None = None, fused_bwd: bool = True,
               fused_ffn: bool = True,
               shard_dims: int | None = None,
               precision=None) -> jax.Array:
    """Whole TT FFN block: ``x (K, N) -> y (K, M)`` through
    ``down(act(up(x)))`` (``down(act(gate(x)) * up(x))`` when
    ``gate_cores`` is given), fused forward AND backward.

    The half-factors of every projection are built exactly once here;
    autodiff chains their cotangents back into per-core gradients.  When
    the megakernel's working set exceeds the VMEM budget
    (``ffn_vmem_fits``, evaluated at the per-device row count
    ``ceil(K / shard_dims)`` — see ``_resolve_shard_dims``) or
    ``fused_ffn=False``, the op takes the two-call path through
    ``_hf_linear`` — the exact computation ``models.layers.mlp_apply``
    performs, bit for bit.
    """
    if interpret is None:
        interpret = kernel_interpret_default()
    sd = _resolve_shard_dims(shard_dims)
    pfmt, afmt = _precision_fmts(precision, x.dtype)
    a1, b1 = tt_half_factors(list(up_cores), up_spec)
    a2, b2 = tt_half_factors(list(down_cores), down_spec)
    ag = bg = None
    if gate_cores is not None:
        ag, bg = tt_half_factors(list(gate_cores), gate_spec)
    if f_logical is None:
        f_logical = min(up_spec.out_dim, down_spec.in_dim)

    M, N, F = down_spec.out_dim, up_spec.in_dim, up_spec.out_dim
    R1, R2 = up_spec.mid_rank, down_spec.mid_rank
    Rg = gate_spec.mid_rank if gate_spec is not None else 0
    itemsize = jnp.dtype(x.dtype).itemsize
    if fused_ffn and ffn_vmem_fits(M, N, F, R1, R2, Rg, itemsize,
                                   K=-(-x.shape[0] // sd)):
        return _ffn_fused(a1, b1, a2, b2, ag, bg, x, act, f_logical,
                          interpret, pfmt, afmt)
    # Two-call fallback: the same slice/act/pad sequence mlp_apply runs.
    u = _hf_linear(a1, b1, x, interpret, fused_bwd, sd,
                   pfmt, afmt)[:, :f_logical]
    if bg is not None:
        g = _hf_linear(ag, bg, x, interpret, fused_bwd, sd,
                       pfmt, afmt)[:, :f_logical]
        h = _FFN_ACTS[act](g) * u
    else:
        h = _FFN_ACTS[act](u)
    if f_logical != down_spec.in_dim:
        h = jnp.pad(h, ((0, 0), (0, down_spec.in_dim - f_logical)))
    return _hf_linear(a2, b2, h, interpret, fused_bwd, sd, pfmt, afmt)


# ---------------------------------------------------------------------------
# Flash attention (fused fwd + single-kernel bwd under a custom VJP).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_fused(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                 window: int | None, group: int, interpret: bool,
                 budget: int | None, afmt: str = "float32") -> jax.Array:
    o, _, _ = _flash_fwd_call(q, k, v, causal, window, group, interpret,
                              budget)
    return o


def _flash_tiles(q, group, budget):
    """``(hb, tq, tk)`` and the scope the launches run under.

    One tile choice (under the caller's budget) feeds BOTH launches, so
    the gate, the forward, and the backward agree on the working set.  A
    head block (``hb > 1``) runs under ``tracing.FLASH_ROWS``, so a trace's
    ``op_name`` shows which flash executions took it."""
    BH, S, D = q.shape
    hb, tq, tk, _, _, _ = choose_attn_tiles(
        S, D, jnp.dtype(q.dtype).itemsize, rows=BH, group=group,
        budget=budget)
    scope = (jax.named_scope(FLASH_ROWS) if hb > 1
             else contextlib.nullcontext())
    return (hb, tq, tk), scope


def _flash_fwd_call(q, k, v, causal, window, group, interpret, budget):
    # The (m, l) statistics are per-row and tile-independent; the
    # backward's recomputed probabilities track the forward's to an ulp
    # (its score dot folds the softmax scale into Q — see
    # flash_backward._bwd_kernel), which the oracle tolerances absorb.
    (hb, tq, tk), scope = _flash_tiles(q, group, budget)
    with scope:
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      group=group, tq=tq, tk=tk, hb=hb,
                                      interpret=interpret,
                                      return_residuals=True)


def _flash_fused_fwd(q, k, v, causal, window, group, interpret, budget,
                     afmt):
    o, m, l = _flash_fwd_call(q, k, v, causal, window, group, interpret,
                              budget)
    # Paper-faithful residual set: (O, m, l) — never the S×S probabilities.
    # With a quantized act format the big residuals (q, k, v, o) are stored
    # per-tensor-scaled; the per-row (m, l) statistics stay f32 (they are
    # O(S) against O(S*D) and softmax stability depends on them).
    if afmt == "float32":
        return o, (q, k, v, o, m, l, None)
    qq, s_q = _prep(q, afmt)
    kq, s_k = _prep(k, afmt)
    vq, s_v = _prep(v, afmt)
    oq, s_o = _prep(o, afmt)
    scales = jnp.stack([s_q, s_k, s_v, s_o])
    return o, (qq, kq, vq, oq, m, l, scales)


def _flash_fused_bwd(causal, window, group, interpret, budget, afmt,
                     residuals, do):
    q, k, v, o, m, l, scales = residuals
    if scales is not None:
        # Dequantize once at BWD entry (transient copies); the saved
        # residual tier between FWD and BWD stayed quantized.
        cdt = do.dtype
        q = _deq(q, scales[0], cdt)
        k = _deq(k, scales[1], cdt)
        v = _deq(v, scales[2], cdt)
        o = _deq(o, scales[3], cdt)
    (hb, tq, tk), scope = _flash_tiles(q, group, budget)
    with scope:
        return flash_attention_bwd_pallas(
            q, k, v, o, m, l, do, causal=causal, window=window, group=group,
            tq=tq, tk=tk, hb=hb, interpret=interpret)


_flash_fused.defvjp(_flash_fused_fwd, _flash_fused_bwd)


def flash_mha_op(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool = True, window: int | None = None,
                 q_chunk: int = 512, kv_chunk: int = 1024,
                 use_kernel: bool = True, interpret: bool | None = None,
                 budget: int | None = None,
                 shard_dims: int | None = None,
                 precision=None) -> jax.Array:
    """``q (B, S, H, D); k, v (B, S, KV, D) -> (B, S, H, D)``, trainable.

    The fused path runs the flash forward and the single-kernel flash
    backward with only ``(O, m, l)`` saved between them.  When the
    backward's VMEM working set exceeds ``budget`` (default: the kernel
    VMEM budget) — or ``use_kernel=False`` — the op silently takes the
    pure-JAX ``blockwise_attention`` path under plain autodiff, with the
    given chunk sizes.  ``core.memory_ledger`` gates on the same
    ``attn_bwd_vmem_fits``, so ledger and dispatch cannot drift.

    ``shard_dims`` is accepted for API symmetry with the other ops: row
    (batch) sharding leaves the per-grid-step (S, D) working set — the
    only thing ``attn_bwd_vmem_fits`` depends on — unchanged, so the
    predicate is already per-shard and the hint needs no arithmetic here.
    The head block (``choose_attn_tiles``) divides the B·H rows the
    launch sees.
    """
    del shard_dims
    B, S, H, D = q.shape
    KV = k.shape[2]
    group = H // KV
    itemsize = jnp.dtype(q.dtype).itemsize
    if not use_kernel or not attn_bwd_vmem_fits(S, D, itemsize,
                                                budget=budget):
        # Lazy import: kernels must not depend on models at module scope.
        from repro.models.attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   q_chunk=q_chunk, kv_chunk=kv_chunk)
    if interpret is None:
        interpret = kernel_interpret_default()
    _, afmt = _precision_fmts(precision, q.dtype)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    o = _flash_fused(qf, kf, vf, causal, window, group, interpret, budget,
                     afmt)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Decode serving ops (forward-only — no VJP; sampling never differentiates).
# ---------------------------------------------------------------------------


def flash_decode_op(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    pos0: jax.Array, *, window: int | None = None,
                    use_kernel: bool = True, interpret: bool | None = None,
                    budget: int | None = None) -> jax.Array:
    """One decode attention step against a paged KV cache.

    ``q (B, H, D)`` — one query row per live stream; ``k_pages``/``v_pages``
    ``(NP, KV, P, D)`` — the physical page pools; ``page_table (B, NPmax)``,
    ``lengths (B,)``, ``pos0 (B,)`` — each stream's logical view (see
    ``flash_decode.flash_decode_pallas``).  GQA is the reshape
    ``(B, KV, H//KV, D)``: query head ``h`` shares KV head ``h // group``,
    matching ``models.attention.decode_attention``'s repeat layout.

    When the working set exceeds ``budget`` — or ``use_kernel=False`` —
    the op takes ``paged_decode_ref``, which executes the identical
    primitive sequence: fallback and kernel are bitwise-comparable, and
    ``core.memory_ledger`` gates its DECODE attention row on the same
    ``decode_attn_vmem_fits``.
    """
    B, H, D = q.shape
    KV, P = k_pages.shape[1], k_pages.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    itemsize = jnp.dtype(q.dtype).itemsize
    if not use_kernel or not decode_attn_vmem_fits(G, D, P, itemsize,
                                                   budget=budget):
        o = paged_decode_ref(qg, k_pages, v_pages, page_table, lengths,
                             pos0, window=window)
    else:
        if interpret is None:
            interpret = kernel_interpret_default()
        o = flash_decode_pallas(qg, k_pages, v_pages, page_table, lengths,
                                pos0, window=window, interpret=interpret)
    return o.reshape(B, H, D)


def btt_linear_decode_op(cores, x: jax.Array, spec: TTSpec, *,
                         use_kernel: bool = True,
                         interpret: bool | None = None,
                         precision=None) -> jax.Array:
    """``x (B, N) -> y (B, M)``: the BTT linear at decode shapes — row tiles
    at the dtype sublane granule instead of the training 32-row blocks.
    Forward-only.  Falls back to the training-tile launch when the decode
    working set exceeds VMEM (same predicate as the ledger's DECODE rows).

    ``precision.param_dtype`` serves the half-factors from quantized-at-rest
    storage: decode is forward-only, so the round-trip
    (``quant.cast_format``) IS the storage semantics — the ledger's DECODE
    weight rows account the stored bytes."""
    if not use_kernel:
        return tt_forward_btt(cores, x, spec)
    if interpret is None:
        interpret = kernel_interpret_default()
    a, b = tt_half_factors(list(cores), spec)
    pfmt, _ = _precision_fmts(precision, x.dtype)
    if pfmt != "float32":
        a = _quant.cast_format(a, pfmt)
        b = _quant.cast_format(b, pfmt)
    itemsize = jnp.dtype(x.dtype).itemsize
    if decode_linear_vmem_fits(a.shape[0], a.shape[1], itemsize,
                               B=x.shape[0]):
        return btt_linear_decode_pallas(x, b, a, interpret=interpret)
    return btt_linear_pallas(x, b, a, interpret=interpret)


def btt_ffn_decode_op(up_cores, down_cores, gate_cores, x: jax.Array,
                      up_spec: TTSpec, down_spec: TTSpec,
                      gate_spec: TTSpec | None = None, *, act: str = "gelu",
                      f_logical: int | None = None,
                      interpret: bool | None = None,
                      precision=None) -> jax.Array:
    """Whole TT FFN block at decode shapes, forward-only: the megakernel
    with sublane-granule row tiles when it fits VMEM
    (``decode_ffn_vmem_fits`` — the ledger's DECODE FFN row gates on the
    same predicate), else the two-call decode-linear path — the exact
    slice/act/pad sequence ``btt_ffn_op``'s fallback runs.
    ``precision.param_dtype`` serves every projection's half-factors from
    quantized-at-rest storage (see ``btt_linear_decode_op``)."""
    if interpret is None:
        interpret = kernel_interpret_default()
    a1, b1 = tt_half_factors(list(up_cores), up_spec)
    a2, b2 = tt_half_factors(list(down_cores), down_spec)
    ag = bg = None
    if gate_cores is not None:
        ag, bg = tt_half_factors(list(gate_cores), gate_spec)
    pfmt, _ = _precision_fmts(precision, x.dtype)
    if pfmt != "float32":
        a1, b1, a2, b2 = (_quant.cast_format(v, pfmt)
                          for v in (a1, b1, a2, b2))
        if bg is not None:
            ag, bg = (_quant.cast_format(v, pfmt) for v in (ag, bg))
    if f_logical is None:
        f_logical = min(up_spec.out_dim, down_spec.in_dim)

    M, N, F = down_spec.out_dim, up_spec.in_dim, up_spec.out_dim
    R1, R2 = up_spec.mid_rank, down_spec.mid_rank
    Rg = gate_spec.mid_rank if gate_spec is not None else 0
    itemsize = jnp.dtype(x.dtype).itemsize
    if decode_ffn_vmem_fits(M, N, F, R1, R2, Rg, itemsize, B=x.shape[0]):
        return btt_ffn_decode_pallas(x, b1, a1, b2, a2, bg, ag, act=act,
                                     f_logical=f_logical,
                                     interpret=interpret)
    u = btt_linear_decode_pallas(x, b1, a1,
                                 interpret=interpret)[:, :f_logical]
    if bg is not None:
        g = btt_linear_decode_pallas(x, bg, ag,
                                     interpret=interpret)[:, :f_logical]
        h = _FFN_ACTS[act](g) * u
    else:
        h = _FFN_ACTS[act](u)
    if f_logical != down_spec.in_dim:
        h = jnp.pad(h, ((0, 0), (0, down_spec.in_dim - f_logical)))
    return btt_linear_decode_pallas(h, b2, a2, interpret=interpret)


# ---------------------------------------------------------------------------
# TTM embedding (one-hot kernel when eligible).
# ---------------------------------------------------------------------------


def _ttm_kernel_eligible(spec: TTMSpec) -> bool:
    if spec.d != 3:
        return False
    core_bytes = sum(int(np.prod(s)) * 4 for s in spec.core_shapes())
    return core_bytes <= _VMEM_CORE_BUDGET


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _ttm_kernel_fused(cores: tuple, oh: tuple, spec_dims: tuple,
                      interpret: bool) -> jax.Array:
    return ttm_embed_pallas(oh, cores, spec_dims=spec_dims,
                            interpret=interpret)


def _ttm_kernel_fwd(cores, oh, spec_dims, interpret):
    y = ttm_embed_pallas(oh, cores, spec_dims=spec_dims, interpret=interpret)
    return y, (cores, oh)


def _ttm_kernel_bwd(spec_dims, interpret, residuals, gy):
    # Core gradients via autodiff of the pure-jnp one-hot chain — the same
    # stage-A..E math the kernel executes (paper Eq. (12): scatter-free,
    # the one-hot GEMMs transpose into the scatter-add).
    cores, oh = residuals
    from .ref import ttm_embed_ref

    _, vjp = jax.vjp(
        lambda c, o: ttm_embed_ref(o, c).astype(gy.dtype), cores, oh)
    gc, goh = vjp(gy)
    return gc, goh


_ttm_kernel_fused.defvjp(_ttm_kernel_fwd, _ttm_kernel_bwd)


def ttm_embed_op(cores, ids: jax.Array, spec: TTMSpec, *,
                 use_kernel: bool = True,
                 interpret: bool | None = None) -> jax.Array:
    """``ids (...,) int32 -> (..., H)`` TTM lookup."""
    if not use_kernel or not _ttm_kernel_eligible(spec):
        return ttm_lookup(cores, ids, spec)
    if interpret is None:
        interpret = kernel_interpret_default()
    batch_shape = ids.shape
    flat = ids.reshape(-1)
    dg = token_digits(flat, spec.vocab_factors)  # (K, 3)
    oh = tuple(
        jax.nn.one_hot(dg[:, k], spec.vocab_factors[k], dtype=cores[0].dtype)
        for k in range(3)
    )
    rs = spec.ranks
    spec_dims = (tuple(spec.vocab_factors), tuple(spec.hidden_factors),
                 (rs[1], rs[2]))
    out = _ttm_kernel_fused(tuple(cores), oh, spec_dims, interpret)
    return out.reshape(batch_shape + (spec.hidden_dim,))
