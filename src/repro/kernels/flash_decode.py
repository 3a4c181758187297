"""Pallas TPU kernel: flash-decode attention against a paged KV cache.

The training kernels (PRs 1-6) close the paper's on-chip story for every
*training* stage; this module is the serving mirror.  At decode time each
stream contributes ONE query row per step, and the whole working set the
paper's framework keeps on chip — TT cores, half-factors, softmax state —
still fits, so the only HBM traffic that matters is the KV cache itself.
FTRANS (arXiv 2007.08563) makes the same observation for block-compressed
transformer inference: the energy win compounds when the cache streams once
and nothing else moves.

The cache is **paged** (vLLM-style): physical pages of ``P`` rows live in a
shared pool ``(NP, KV, P, D)`` and each request owns an ordered list of page
ids (its page table).  The kernel never sees a contiguous cache:

  grid = (B, KVh, NPmax), page axis innermost (sequential).
  q block (1, 1, Gp, Dp)  — one stream's query rows for one KV head, ALL
                            GQA group members together (the repeat happens
                            in the block layout, never in memory)
  k/v block (1, 1, P, Dp) — ONE page, fetched page-table-indirectly: the
                            BlockSpec index map reads ``pt[b, p]`` from the
                            scalar-prefetched page table, so only pages the
                            request actually owns are addressed — physical
                            page order is invisible to the math
  o block  (1, 1, Gp, Dp) — written once per (b, h)
  m/l/acc scratch         — online-softmax state carried in VMEM across the
                            page axis (the flash dataflow, single Q row)

Logical positions are slot-ordered: row ``i`` of page-table slot ``p`` is
position ``pos0 + p·P + i`` (``pos0 > 0`` after ring eviction on windowed
layers — whole out-of-window pages are freed by the cache manager, and the
in-page tail is masked here).  Dead pages (``p·P >= len - pos0``) are
skipped via ``pl.when``; ragged page tails are masked by ``lpos < len``.

``paged_decode_ref`` is the pure-JAX fallback AND the oracle: it scans the
page axis with the identical primitive sequence (same ``dot_general`` dims,
same select order), so the two paths are bitwise-comparable in tests and
the VMEM-budget fallback in ``ops.flash_decode_op`` cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .btt_linear import VMEM_BUDGET, _round_up
from .flash_attention import NEG_INF

__all__ = [
    "flash_decode_pallas",
    "paged_decode_ref",
    "choose_decode_attn_tiles",
    "decode_attn_vmem_fits",
    "decode_attn_stage_vmem_bytes",
    "decode_attn_flops",
    "fused_decode_attn_hbm_bytes",
    "unfused_decode_attn_hbm_bytes",
    "DEFAULT_PAGE_SIZE",
]

DEFAULT_PAGE_SIZE = 64


# ---------------------------------------------------------------------------
# Tile chooser — single residency source for kernel, op gate, and ledger.
# ---------------------------------------------------------------------------


def choose_decode_attn_tiles(G: int, D: int, P: int, itemsize: int, *,
                             budget: int | None = None
                             ) -> tuple[int, int, int]:
    """(gp, dp, vmem_bytes) for one flash-decode grid step.

    ``G`` = GQA group size (query heads per KV head), ``D`` = head dim,
    ``P`` = page size.  The working set is a single query-row tile plus one
    page — there is nothing to shrink (the page size is the cache layout,
    chosen by the serving config), so this chooser only reports; callers
    gate on :func:`decode_attn_vmem_fits` and fall back to the pure-JAX
    paged reference when an oversized page overflows the budget.
    """
    gp = _round_up(G, 8)        # f32 sublane granule; bf16 pads further
    dp = _round_up(D, 128)
    # q + o blocks, k + v page blocks, m/l/acc f32 scratch, (gp, P) score.
    vmem = (2 * gp * dp * itemsize + 2 * P * dp * itemsize
            + gp * (dp + 2) * 4 + gp * P * 4)
    return gp, dp, vmem


def decode_attn_vmem_fits(G: int, D: int, P: int, itemsize: int, *,
                          budget: int | None = None) -> bool:
    """True iff the flash-decode working set fits the kernel VMEM budget.

    THE dispatch predicate: ``ops.flash_decode_op`` takes the kernel path
    iff this holds, and ``core.memory_ledger`` gates its DECODE attention
    row on it too.
    """
    budget = budget or VMEM_BUDGET
    return choose_decode_attn_tiles(G, D, P, itemsize)[2] <= budget


def decode_attn_stage_vmem_bytes(G: int, D: int, P: int, itemsize: int, *,
                                 fused: bool = True,
                                 budget: int | None = None) -> int:
    """VMEM working set the decode attention stage ACTUALLY launches: the
    kernel's (chooser-derived) when ``fused`` and it fits, else 0 (the
    fallback is pure-JAX — no Pallas launch)."""
    if not fused or not decode_attn_vmem_fits(G, D, P, itemsize,
                                              budget=budget):
        return 0
    return choose_decode_attn_tiles(G, D, P, itemsize)[2]


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------


def _kernel(pt_ref, len_ref, pos0_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, np_max: int, page: int, scale: float,
            window: int | None):
    del pt_ref  # consumed by the BlockSpec index maps
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    pos0 = pos0_ref[b]
    live = p * page < length - pos0   # page holds at least one valid row

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]                           # (Gp, Dp)
        k = k_ref[0, 0]                           # (P, Dp)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        lpos = pos0 + p * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = lpos < length
        if window is not None:
            mask &= lpos >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                       # (Gp, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)                   # (Gp, P) f32
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + pr.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0]                           # (P, Dp)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(p == np_max - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_pallas(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        page_table: jax.Array, lengths: jax.Array,
                        pos0: jax.Array, *, window: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """``q (B, KV, G, D); k/v pages (NP, KV, P, D) -> o (B, KV, G, D)``.

    ``page_table (B, NPmax) int32`` maps each request's logical page slots
    to physical page ids; ``lengths (B,) int32`` is the number of valid
    cache rows per request (INCLUDING the current token, written before
    attending); ``pos0 (B,) int32`` the logical position of slot 0 row 0
    (nonzero after ring eviction on windowed layers).  Slots at or past
    ``ceil((len - pos0) / P)`` are dead: their table entries may point
    anywhere valid and are never read into the math.
    """
    B, KV, G, D = q.shape
    NP, _, P, _ = k_pages.shape
    np_max = page_table.shape[1]
    scale = 1.0 / (D ** 0.5)
    itemsize = jnp.dtype(q.dtype).itemsize
    gp, dp, _ = choose_decode_attn_tiles(G, D, P, itemsize)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, gp - G), (0, dp - D)))
    kp = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, dp - D)))
    vp = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, dp - D)))

    grid = (B, KV, np_max)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # page_table, lengths, pos0
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gp, dp),
                         lambda b, h, p, pt, ln, p0: (b, h, 0, 0)),
            # Page-table indirection: the k/v block for grid step (b, ·, p)
            # is physical page pt[b, p] — only owned pages are addressed.
            pl.BlockSpec((1, 1, P, dp),
                         lambda b, h, p, pt, ln, p0: (pt[b, p], h, 0, 0)),
            pl.BlockSpec((1, 1, P, dp),
                         lambda b, h, p, pt, ln, p0: (pt[b, p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, dp),
                               lambda b, h, p, pt, ln, p0: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, 1), jnp.float32),     # m
            pltpu.VMEM((gp, 1), jnp.float32),     # l
            pltpu.VMEM((gp, dp), jnp.float32),    # acc
        ],
    )
    o = pl.pallas_call(
        functools.partial(_kernel, np_max=np_max, page=P, scale=scale,
                          window=window),
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, gp, dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      pos0.astype(jnp.int32), qp, kp, vp)
    return o[:, :, :G, :D]


# ---------------------------------------------------------------------------
# Pure-JAX paged reference — fallback path AND bitwise oracle.
# ---------------------------------------------------------------------------


def paged_decode_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_table: jax.Array, lengths: jax.Array,
                     pos0: jax.Array, *,
                     window: int | None = None) -> jax.Array:
    """Same signature/result as :func:`flash_decode_pallas`, pure JAX.

    Scans the page axis with the IDENTICAL primitive sequence the kernel
    executes (same ``dot_general`` dimension numbers, same mask/select
    order) on the SAME sublane/lane-padded operand shapes (XLA picks its
    dot reduction strategy per shape, so matching tiles is what makes the
    two paths bitwise-comparable on CPU — the parity tests in
    ``tests/test_flash_decode.py`` hold both to that).
    """
    B, KV, G, D = q.shape
    P = k_pages.shape[2]
    np_max = page_table.shape[1]
    scale = 1.0 / (D ** 0.5)
    gp, dp, _ = choose_decode_attn_tiles(
        G, D, P, jnp.dtype(q.dtype).itemsize)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - G), (0, dp - D)))
    k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, dp - D)))
    v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, dp - D)))

    def one_request(qb, pt_b, len_b, pos0_b):
        kg = k_pages[pt_b]        # (NPmax, KV, P, D)
        vg = v_pages[pt_b]

        def one_head(qh, kh, vh):  # qh (gp, dp); kh/vh (NPmax, P, dp)
            m0 = jnp.full((gp, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((gp, 1), jnp.float32)
            acc0 = jnp.zeros((gp, dp), jnp.float32)

            def step(carry, inp):
                m, l, acc = carry
                p_idx, kp_, vp_ = inp
                live = p_idx * P < len_b - pos0_b
                s = jax.lax.dot_general(
                    qh, kp_, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                lpos = pos0_b + p_idx * P + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                mask = lpos < len_b
                if window is not None:
                    mask &= lpos >= len_b - window
                s = jnp.where(mask, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
                pr = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_new = l * corr + pr.sum(axis=1, keepdims=True)
                acc_new = acc * corr + jax.lax.dot_general(
                    pr.astype(vp_.dtype), vp_, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                keep = lambda new, old: jnp.where(live, new, old)  # noqa: E731
                return (keep(m_new, m), keep(l_new, l),
                        keep(acc_new, acc)), None

            (m, l, acc), _ = jax.lax.scan(
                step, (m0, l0, acc0),
                (jnp.arange(np_max), kh, vh))
            return (acc / jnp.maximum(l, 1e-30)).astype(qh.dtype)

        # vmap over KV heads: kg (NPmax, KV, P, D) -> per-head (NPmax, P, D)
        return jax.vmap(one_head, in_axes=(0, 1, 1))(qb, kg, vg)

    out = jax.vmap(one_request)(q, page_table.astype(jnp.int32),
                                lengths.astype(jnp.int32),
                                pos0.astype(jnp.int32))
    return out[:, :, :G, :D]


# ---------------------------------------------------------------------------
# Analytic FLOP / HBM-byte models (bench_decode + ledger rows).
# ---------------------------------------------------------------------------


def decode_attn_flops(B: int, H: int, D: int, length: int) -> int:
    """FLOPs of one decode attention step over the valid cache: two matmuls
    (qKᵀ, pV), 2·D FLOPs per live score element."""
    return B * H * length * 2 * D * 2


def fused_decode_attn_hbm_bytes(B: int, H: int, KV: int, D: int, P: int,
                                n_pages: int, itemsize: int) -> int:
    """HBM bytes one flash-decode launch moves (tile-derived).

    q read once per (b, h), k/v pages fetched page-table-indirectly —
    ``n_pages`` live pages per request, each once per KV head (dead slots
    are clamped by the table and never re-fetched) — o written once.  No
    contiguous cache copy, no score row, no probability row: the softmax
    state lives in VMEM scratch.  Padded bytes are real bytes on the wire.
    """
    G = H // KV
    gp, dp, _ = choose_decode_attn_tiles(G, D, P, itemsize)
    q_io = 2 * B * KV * gp * dp * itemsize          # q read + o written
    kv = B * KV * n_pages * 2 * P * dp * itemsize   # pages streamed once
    return q_io + kv


def unfused_decode_attn_hbm_bytes(B: int, H: int, KV: int, D: int,
                                  S: int, itemsize: int) -> int:
    """HBM bytes of the unfused decode path over a length-``S`` cache.

    Counts, generously to XLA (each tensor once per producing/consuming
    pass): the page gather materializing a contiguous ``(B, S, KV, D)``
    copy (pool read + copy write), the copy re-read by qKᵀ, the
    ``(B, H, S)`` f32 score row written, read+rewritten by the softmax,
    and the probability row re-read with the second copy pass for pV.
    This is what the paged kernel deletes: with it the cache streams
    exactly once and no row-sized intermediate exists.
    """
    cache = B * S * KV * D * itemsize
    gather = 2 * cache                       # pool read + contiguous write
    qk = B * H * D * itemsize + cache        # q read + copy re-read
    scores = 3 * B * H * S * 4               # s written; softmax rd+wr
    av = B * H * S * 4 + cache               # p re-read + copy re-read
    o = B * H * D * itemsize
    return gather + qk + scores + av + o
