"""Pallas TPU kernel: flash attention (causal / sliding-window, GQA-aware).

Why it exists here: §Roofline shows the prefill/train cells memory-bound,
and loop-nest attribution (EXPERIMENTS.md §Perf) pins most of that traffic
on the pure-JAX blockwise attention — its online-softmax state (m, l, acc)
is a scan carry that XLA round-trips through HBM on every KV chunk.  The
fix is structural: keep the state in VMEM scratch across the KV axis of the
grid, so HBM sees only Q/K/V reads and one O write — the flash-attention
dataflow, here as the TPU analogue of the paper's "intermediates never
leave chip" principle (Sec. V-B2).

Grid = (B·H, S/TQ, S/TK), KV innermost (sequential); GQA without
materializing repeated KV: the K/V BlockSpec index maps query-head ``h`` to
its KV head ``h // group`` — the repeat happens in the index computation,
not in memory.  Fully-masked causal blocks are skipped via ``pl.when``.

Short sequences take a head block instead (``hb > 1``, chosen by
``flash_backward.choose_attn_tiles`` when the whole sequence is one tile):
grid = (B·H/hb,), and one step takes ``hb`` whole (batch·head) rows — q and
o blocks ``(hb, S, D)``, k/v blocks ``(hb/group, S, D)`` of the KV heads
those query heads share, statistics ``(hb, S)`` — at their own S and D, so
nothing is padded and no online-softmax state is carried.  At S = 32 a
(batch, head) pair is a few KB of data, and as a grid step of its own it
costs the step's fixed overhead; a head block amortizes that overhead.

``return_residuals=True`` additionally emits the per-row softmax statistics
``(m, l)`` — the residuals the fused backward (``flash_backward.py``)
recomputes probability tiles from, so training never saves the S×S
probability matrix.  Tiles may go as low as 32 rows (sublane granule) so the
paper's S=32 regime launches unpadded on the sequence axis; lane padding of
sub-128 tiles is left to Mosaic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "DEFAULT_TQ", "DEFAULT_TK"]

DEFAULT_TQ = 256
DEFAULT_TK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, *refs,
            nk: int, tq: int, tk: int, scale: float, causal: bool,
            window: int | None, s_real: int, emit_stats: bool):
    if emit_stats:
        o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = iq * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kpos = ik * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)

    # Skip blocks that the causal mask fully zeroes (window handled by the
    # in-block mask; its dead blocks are rarer and not worth the branch).
    if causal:
        live = ik * tk <= iq * tq + tq - 1       # some kpos <= some qpos
    else:
        live = jnp.asarray(True)

    @pl.when(live)
    def _step():
        q = q_ref[0]                              # (TQ, D)
        k = k_ref[0]                              # (TK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = kpos < s_real
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                       # (TQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                    # (TQ, TK) f32
        corr = jnp.exp(m_prev - m_new)            # (TQ, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0]                              # (TK, D)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        if emit_stats:
            mo_ref[0] = m_ref[...]
            lo_ref[0] = l_ref[...]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


# ---------------------------------------------------------------------------
# Head-block path: ``hb`` whole (batch·head) rows a grid step.
# ---------------------------------------------------------------------------


def rows_mask(shape: tuple[int, int, int], causal: bool,
              window: int | None):
    """Keep-mask of an ``(hb, S, S)`` score block, None when all is kept."""
    if not causal and window is None:
        return None
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    mask = kpos <= qpos if causal else jnp.ones(shape, bool)
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def repeat_heads(x: jax.Array, group: int) -> jax.Array:
    """``(n, S, D) -> (n·group, S, D)``: each KV head once per query head
    of its group, in the query heads' order (``h -> h // group``)."""
    if group == 1:
        return x
    n, S, D = x.shape
    return jnp.broadcast_to(x[:, None], (n, group, S, D)).reshape(
        n * group, S, D)


def batched_dot(a, b, contract: tuple[int, int]):
    """Batched f32-accumulated product over the leading (head) axis."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _rows_kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
                 window: int | None, group: int, emit_stats: bool):
    """One grid step of the head-block path: the whole key row of ``hb``
    query heads, so the softmax is exact in one pass.  The same products
    and f32 softmax as ``_kernel`` at ``nk = 1``."""
    q = q_ref[...]                                # (hb, S, D)
    k = repeat_heads(k_ref[...], group)
    v = repeat_heads(v_ref[...], group)
    s = batched_dot(q, k, (2, 2)) * scale         # (hb, S, S) f32
    mask = rows_mask(s.shape, causal, window)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=2, keepdims=True)              # (hb, S, 1)
    p = jnp.exp(s - m)
    l = p.sum(axis=2, keepdims=True)
    acc = batched_dot(p.astype(v.dtype), v, (2, 1))
    refs[0][...] = (acc / jnp.maximum(l, 1e-30)).astype(refs[0].dtype)
    if emit_stats:
        refs[1][...] = m[..., 0]                  # (hb, S): S on lanes
        refs[2][...] = l[..., 0]


def _flash_rows(q, k, v, *, causal, window, group, hb, interpret,
                return_residuals):
    BH, S, D = q.shape
    if BH % hb or hb % group:
        raise ValueError(f"head block {hb} must divide {BH} rows and be a "
                         f"multiple of the group {group}")

    def block(n):
        return pl.BlockSpec((n, S, D), lambda i: (i, 0, 0))

    out_specs = [block(hb)]
    out_shape = [jax.ShapeDtypeStruct((BH, S, D), q.dtype)]
    if return_residuals:
        stat = pl.BlockSpec((hb, S), lambda i: (i, 0))
        out_specs += [stat, stat]
        out_shape += [jax.ShapeDtypeStruct((BH, S), jnp.float32)] * 2
    res = pl.pallas_call(
        functools.partial(_rows_kernel, scale=1.0 / math.sqrt(D),
                          causal=causal, window=window, group=group,
                          emit_stats=return_residuals),
        name="flash_fwd",
        grid=(BH // hb,),
        in_specs=[block(hb), block(hb // group), block(hb // group)],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, k, v)
    return tuple(res) if return_residuals else res[0]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "group", "tq", "tk", "hb", "interpret",
    "return_residuals"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           group: int = 1, tq: int | None = None,
                           tk: int | None = None, hb: int = 1,
                           interpret: bool = False,
                           return_residuals: bool = False):
    """``q (BH, S, D); k, v (BH/group, S, D) -> o (BH, S, D)``.

    ``group`` = GQA group size (query heads per KV head); the K/V block
    index maps ``h -> h // group`` so repeated KV never materializes.
    S is padded to the tile grid; padded KV columns are masked, padded Q
    rows sliced off.

    ``hb > 1`` takes the head-block path (module docstring): ``hb`` rows a
    grid step, each over its whole sequence; ``hb`` divides BH and is a
    multiple of ``group``, and ``tq``/``tk`` are unused.

    ``return_residuals=True`` returns ``(o, m, l)`` with ``m, l (BH, S)``
    f32 — the per-row softmax max / normalizer the fused backward kernel
    needs to recompute probability tiles without the S×S matrix.
    """
    if hb > 1:
        return _flash_rows(q, k, v, causal=causal, window=window,
                           group=group, hb=hb, interpret=interpret,
                           return_residuals=return_residuals)
    BH, S, D = q.shape
    scale = 1.0 / math.sqrt(D)
    tq = tq or min(DEFAULT_TQ, _round_up(S, 128))
    tk = tk or min(DEFAULT_TK, _round_up(S, 128))
    sp = _round_up(S, max(tq, tk))
    dp_ = _round_up(D, 128)
    qp = jnp.pad(q, ((0, 0), (0, sp - S), (0, dp_ - D)))
    kp = jnp.pad(k, ((0, 0), (0, sp - S), (0, dp_ - D)))
    vp = jnp.pad(v, ((0, 0), (0, sp - S), (0, dp_ - D)))
    nq, nk = sp // tq, sp // tk
    grid = (BH, nq, nk)

    out_specs = [pl.BlockSpec((1, tq, dp_), lambda h, i, j: (h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, sp, dp_), q.dtype)]
    if return_residuals:
        # The statistics carry a trailing unit axis inside the launch: a
        # (1, tq) block of a (BH, S) array breaks Mosaic's rule that a
        # block's last two dims be (8, 128)-aligned or whole.
        stat = pl.BlockSpec((1, tq, 1), lambda h, i, j: (h, i, 0))
        out_specs += [stat, stat]
        out_shape += [jax.ShapeDtypeStruct((BH, sp, 1), jnp.float32)] * 2

    res = pl.pallas_call(
        functools.partial(_kernel, nk=nk, tq=tq, tk=tk, scale=scale,
                          causal=causal, window=window, s_real=S,
                          emit_stats=return_residuals),
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, dp_), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, tk, dp_), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, tk, dp_), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),     # m
            pltpu.VMEM((tq, 1), jnp.float32),     # l
            pltpu.VMEM((tq, dp_), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    if return_residuals:
        out, m, l = res
        return out[:, :S, :D], m[:, :S, 0], l[:, :S, 0]
    return res[0][:, :S, :D]
