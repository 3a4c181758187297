"""Pallas TPU kernels: fused parameter-update (PU) stage.

The paper's framework keeps *every* training stage on chip (Sec. III-A):
FWD, BWD, and the parameter update (step 3, "PU") all run against the
BRAM/URAM budget.  FWD/BWD are already fused (``btt_linear.py`` +
the custom VJP in ``ops.py``); this module fuses the third stage.  The idiom
follows Count-Sketch Optimizers' dense path — "update the auxiliary
variables and perform the gradient update in a single fused kernel" — so a
training step touches each optimizer buffer exactly once.

Why fuse an elementwise update?  Unfused, an AdamW step is ~10 XLA HLOs per
parameter leaf; each moment buffer round-trips HBM<->VMEM several times
(read m, write m', read m' again for the step, ...).  Fused, the kernel
tiles **flattened** parameter / gradient / moment buffers through VMEM once:
per grid step it reads one (rows, lanes) block of each operand, computes the
entire update (moment EMAs, bias correction, weight decay, parameter delta)
in registers/VMEM f32, and writes the block back.  ``input_output_aliases``
makes the update in-place at the *packed-buffer* level — the kernel itself
never double-buffers optimizer state, which matters when the budget is a
few MB of on-chip SRAM.  The pack/unpack reshapes around the kernel are
ordinary XLA ops: leaves still round-trip into the packed layout each step
(XLA fuses but does not alias through concatenate/pad), so end-to-end
leaf-level aliasing awaits storing optimizer state flat-packed between
steps — noted as future work in docs/memory_optimizations.md.

Layout: each dtype-group of leaves is raveled and concatenated into one 1-D
buffer, zero-padded to a (rows, LANES) tile grid — one kernel launch per
*training step*, not per core.  This is the PU analogue of the packed core
buffers in ``core.cost_model.tpu_packing_efficiency``: TT cores are tiny
(a (12, 8, 12) core wastes >90% of an (8, 128) tile stored alone), so the
flat packing is also what makes the PU stage's VMEM residency minimal.

All kernels run ``interpret=True`` on CPU (the validation path, like every
other kernel here); TPU is the target.  Pure-JAX fallbacks live in
``optim.optimizers`` (``fused=False``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant as _quant
from repro.core.cost_model import sublane as _cm_sublane

from .ops import kernel_interpret_default

__all__ = [
    "fused_sgd_update",
    "fused_adamw_update",
    "fused_adamw_update_quant",
    "quant_master_pack",
    "quant_master_unpack",
    "quant_pu_hbm_bytes",
    "sketched_adamw_update",
    "sketched_adamw_update_quant",
    "pack_leaves",
    "unpack_leaves",
    "pu_block_shape",
    "fused_pu_hbm_bytes",
    "unfused_pu_hbm_bytes",
    "sketched_pu_hbm_bytes",
    "sketch_bucket_ids",
    "sketch_signs",
    "sketch_state_bytes",
    "sketch_pu_vmem_bytes",
    "sketch_pu_fits",
    "default_sketch_width",
    "SKETCH_DEPTH_DEFAULT",
]

LANES = 1024          # minor dim of the flattened tile grid (8 x 128 lanes)
BLOCK_ROWS = 256      # rows per grid step: (256, 1024) f32 block = 1 MB


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def pu_block_shape(n_elems: int) -> tuple[int, int, int]:
    """(block_rows, padded_rows, lanes) for a flat buffer of ``n_elems``.

    Small buffers (the whole ATIS TT model is ~0.3M elements) collapse to a
    single sublane-aligned block; large ones stream BLOCK_ROWS-row tiles.
    """
    lanes = LANES if n_elems >= LANES else 128
    rows = max(1, -(-n_elems // lanes))
    br = min(BLOCK_ROWS, _round_up(rows, 8))
    return br, _round_up(rows, br), lanes


def pack_leaves(leaves: Sequence[jax.Array], dtype, rows_p: int,
                lanes: int) -> jax.Array:
    """Ravel+concat ``leaves`` into one padded (rows_p, lanes) buffer."""
    flat = jnp.concatenate([jnp.ravel(x).astype(dtype) for x in leaves])
    return jnp.pad(flat, (0, rows_p * lanes - flat.size)).reshape(rows_p, lanes)


def unpack_leaves(buf: jax.Array, shapes: Sequence[tuple[int, ...]],
                  dtypes: Sequence[Any]) -> list[jax.Array]:
    """Inverse of :func:`pack_leaves` (slices are static; XLA fuses them)."""
    flat = buf.reshape(-1)
    sizes = [int(np.prod(s)) for s in shapes]
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return [
        jax.lax.slice(flat, (int(offs[i]),), (int(offs[i + 1]),))
        .reshape(shapes[i]).astype(dtypes[i])
        for i in range(len(shapes))
    ]


# ---------------------------------------------------------------------------
# Kernel bodies.  Grid is 1-D over row blocks; scalars ride in SMEM as a
# (1, k) f32 vector (TPU scalars must be 2-D); hyperparameters that are
# Python floats are baked in as compile-time constants via partial.
# ---------------------------------------------------------------------------


def _sgd_kernel(scal_ref, p_ref, g_ref, o_ref):
    lr = scal_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    o_ref[...] = (p - lr * g_ref[...]).astype(o_ref.dtype)


def _sgd_momentum_kernel(scal_ref, p_ref, mu_ref, g_ref, o_ref, omu_ref, *,
                         momentum: float):
    lr = scal_ref[0, 0]
    mu = momentum * mu_ref[...] + g_ref[...]
    p = p_ref[...].astype(jnp.float32)
    omu_ref[...] = mu
    o_ref[...] = (p - lr * mu).astype(o_ref.dtype)


def _adamw_kernel(scal_ref, p_ref, m_ref, v_ref, g_ref,
                  o_ref, om_ref, ov_ref, *,
                  b1: float, b2: float, eps: float, weight_decay: float):
    lr = scal_ref[0, 0]
    bc1 = scal_ref[0, 2]
    bc2 = scal_ref[0, 3]
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * jnp.square(g)
    p = p_ref[...].astype(jnp.float32)
    step = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if weight_decay:
        step = step + lr * weight_decay * p
    om_ref[...] = m
    ov_ref[...] = v
    o_ref[...] = (p - step).astype(o_ref.dtype)


def _pu_call(kernel, name: str, scal: jax.Array, bufs: Sequence[jax.Array],
             n_outs: int, br: int, interpret: bool) -> tuple[jax.Array, ...]:
    """Launch a PU kernel over flat (rows_p, lanes) buffers.

    ``bufs`` order is (aliased..., grads): param buffer first (its dtype is
    the first output's dtype), then f32 moment buffers, grads last.  The
    first ``n_outs`` bufs are aliased to the outputs, so donated inputs
    update in place.  ``br`` is the block-row count from the same
    ``pu_block_shape`` call that sized the buffers (rows_p % br == 0).
    """
    rows_p, lanes = bufs[0].shape
    grid = (rows_p // br,)
    blk = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=[_scal_spec()]
        + [blk] * len(bufs),
        out_specs=[blk] * n_outs,
        out_shape=[jax.ShapeDtypeStruct(b.shape, b.dtype)
                   for b in bufs[:n_outs]],
        # scal is input 0; alias param/state inputs onto the outputs.
        input_output_aliases={1 + i: i for i in range(n_outs)},
        interpret=interpret,
    )(scal, *bufs)
    return tuple(out)


def _dtype_groups(leaves: Sequence[jax.Array]) -> list[list[int]]:
    """Indices of ``leaves`` grouped by dtype (one kernel launch per group)."""
    groups: dict[Any, list[int]] = {}
    for i, x in enumerate(leaves):
        groups.setdefault(jnp.dtype(x.dtype), []).append(i)
    return list(groups.values())


def _scal(lr_t, t=0.0, b1: float = 0.0, b2: float = 0.0) -> jax.Array:
    """The SMEM scalar row ``[lr, t, 1 - b1**t, 1 - b2**t]``.

    The AdamW bias corrections are computed here, outside the kernel:
    Mosaic cannot lower ``powf``.
    """
    t = jnp.asarray(t, jnp.float32)
    return jnp.stack([jnp.asarray(lr_t, jnp.float32), t,
                      1.0 - b1 ** t, 1.0 - b2 ** t]).reshape(1, 4)


def _scal_spec() -> pl.BlockSpec:
    return pl.BlockSpec((1, 4), lambda i: (0, 0), memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# Public pytree-level entry points.
# ---------------------------------------------------------------------------


def fused_sgd_update(params, grads, lr_t, *, momentum: float = 0.0,
                     mu=None, interpret: bool | None = None):
    """One fused SGD(+momentum) PU stage over a parameter pytree.

    Returns ``new_params`` (momentum == 0) or ``(new_params, new_mu)``.
    Numerics match the pure-JAX path in ``optim.optimizers.sgd`` (all math
    in f32, params cast back to their storage dtype).
    """
    if interpret is None:
        interpret = kernel_interpret_default()
    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    mu_leaves = treedef.flatten_up_to(mu) if mu is not None else None
    new_p: list = [None] * len(p_leaves)
    new_mu: list = [None] * len(p_leaves)
    scal = _scal(lr_t)
    for idx in _dtype_groups(p_leaves):
        group = [p_leaves[i] for i in idx]
        n = sum(int(np.prod(x.shape)) for x in group)
        br, rows_p, lanes = pu_block_shape(n)
        pdt = group[0].dtype
        pb = pack_leaves(group, pdt, rows_p, lanes)
        gb = pack_leaves([g_leaves[i] for i in idx], jnp.float32, rows_p, lanes)
        shapes = [x.shape for x in group]
        if momentum == 0.0:
            (ob,) = _pu_call(_sgd_kernel, "fused_sgd", scal, [pb, gb], 1,
                             br, interpret)
            outs = unpack_leaves(ob, shapes, [pdt] * len(group))
            for j, i in enumerate(idx):
                new_p[i] = outs[j]
        else:
            mb = pack_leaves([mu_leaves[i] for i in idx], jnp.float32,
                             rows_p, lanes)
            kern = functools.partial(_sgd_momentum_kernel, momentum=momentum)
            ob, omb = _pu_call(kern, "fused_sgd_momentum", scal,
                               [pb, mb, gb], 2, br, interpret)
            outs = unpack_leaves(ob, shapes, [pdt] * len(group))
            mouts = unpack_leaves(omb, shapes, [jnp.float32] * len(group))
            for j, i in enumerate(idx):
                new_p[i], new_mu[i] = outs[j], mouts[j]
    params_out = jax.tree.unflatten(treedef, new_p)
    if momentum == 0.0:
        return params_out
    return params_out, jax.tree.unflatten(treedef, new_mu)


def fused_adamw_update(params, grads, m, v, lr_t, t, *, b1: float,
                       b2: float, eps: float, weight_decay: float,
                       interpret: bool | None = None):
    """One fused AdamW PU stage: ``(new_params, new_m, new_v)``.

    ``t`` is the 1-based step (the bias corrections ride into the kernel
    as SMEM scalars); hyperparameters are compile-time constants.
    """
    if interpret is None:
        interpret = kernel_interpret_default()
    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    m_leaves = treedef.flatten_up_to(m)
    v_leaves = treedef.flatten_up_to(v)
    new_p: list = [None] * len(p_leaves)
    new_m: list = [None] * len(p_leaves)
    new_v: list = [None] * len(p_leaves)
    scal = _scal(lr_t, t, b1, b2)
    kern = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay)
    for idx in _dtype_groups(p_leaves):
        group = [p_leaves[i] for i in idx]
        n = sum(int(np.prod(x.shape)) for x in group)
        br, rows_p, lanes = pu_block_shape(n)
        pdt = group[0].dtype
        pb = pack_leaves(group, pdt, rows_p, lanes)
        mb = pack_leaves([m_leaves[i] for i in idx], jnp.float32, rows_p, lanes)
        vb = pack_leaves([v_leaves[i] for i in idx], jnp.float32, rows_p, lanes)
        gb = pack_leaves([g_leaves[i] for i in idx], jnp.float32, rows_p, lanes)
        ob, omb, ovb = _pu_call(kern, "fused_adamw", scal,
                                [pb, mb, vb, gb], 3, br, interpret)
        shapes = [x.shape for x in group]
        outs = unpack_leaves(ob, shapes, [pdt] * len(group))
        mouts = unpack_leaves(omb, shapes, [jnp.float32] * len(group))
        vouts = unpack_leaves(ovb, shapes, [jnp.float32] * len(group))
        for j, i in enumerate(idx):
            new_p[i], new_m[i], new_v[i] = outs[j], mouts[j], vouts[j]
    return (jax.tree.unflatten(treedef, new_p),
            jax.tree.unflatten(treedef, new_m),
            jax.tree.unflatten(treedef, new_v))


# ---------------------------------------------------------------------------
# Quantized-master AdamW: int8/fp8 params at rest, f32 step in VMEM.
#
# With a quantized storage tier (``core.quant``) the fused PU stage keeps
# the *master* copy of the parameters in int8 / fp8_e4m3 — the only copy;
# there is no shadow f32 master in HBM.  The packed (rows_p, LANES) buffer
# carries one f32 scale per (br, LANES) grid block (the "per_tile"
# granularity of ``PrecisionConfig``), so each kernel step is closed over a
# single block: dequantize the block into VMEM f32, run the identical
# AdamW math as ``_adamw_kernel``, compute the block's new max-abs scale
# IN-KERNEL, and stochastically round the updated block back onto the
# storage grid (``quant.stochastic_round``, counter-keyed by
# (element, step, block id) — bit-reproducible across checkpoint resume).
# Moments stay f32 (or sketched — orthogonal): the round-off each step is
# confined to the parameter write, where SR keeps it zero-mean.
# ---------------------------------------------------------------------------


def _adamw_quant_kernel(scal_ref, pq_ref, ps_ref, m_ref, v_ref, g_ref,
                        oq_ref, ops_ref, om_ref, ov_ref, *,
                        b1: float, b2: float, eps: float,
                        weight_decay: float, fmt: str):
    """One packed block of the quantized-master AdamW PU stage."""
    lr = scal_ref[0, 0]
    t = scal_ref[0, 1]
    bc1 = scal_ref[0, 2]
    bc2 = scal_ref[0, 3]
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * jnp.square(g)
    # In-VMEM dequant of the master block: int8/fp8 tile -> f32 registers.
    p = pq_ref[...].astype(jnp.float32) * ps_ref[0, 0]
    step = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if weight_decay:
        step = step + lr * weight_decay * p
    p_new = p - step
    f = _quant.resolve(fmt)
    s_new = jnp.maximum(jnp.max(jnp.abs(p_new)), _quant._TINY) / f.qmax
    om_ref[...] = m
    ov_ref[...] = v
    ops_ref[0, 0] = s_new
    oq_ref[...] = _quant.stochastic_round(
        p_new / s_new, fmt, t.astype(jnp.int32), pl.program_id(0))


def quant_master_pack(leaves: Sequence[jax.Array], fmt: str
                      ) -> tuple[jax.Array, jax.Array]:
    """Pack param ``leaves`` into the quantized master state ``(pq, ps)``:
    ``pq`` a (rows_p, LANES) storage-dtype buffer, ``ps`` (n_blocks, 1) f32
    per-block scales — the layout the quant PU kernel streams.  Initial
    quantization is round-to-nearest (no step counter exists yet)."""
    f = _quant.resolve(fmt)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    br, rows_p, lanes = pu_block_shape(n)
    pb = pack_leaves(leaves, jnp.float32, rows_p, lanes)
    n_blocks = rows_p // br
    blocks = pb.reshape(n_blocks, br * lanes)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    ps = (jnp.maximum(amax, _quant._TINY) / f.qmax).astype(jnp.float32)
    z = jnp.clip(blocks / ps, -f.qmax, f.qmax)
    q = jnp.round(z) if f.name == "int8" else z
    pq = q.astype(f.dtype).reshape(rows_p, lanes)
    return pq, ps


def quant_master_unpack(pq: jax.Array, ps: jax.Array,
                        shapes: Sequence[tuple[int, ...]],
                        dtypes: Sequence[Any]) -> list[jax.Array]:
    """Dequantized (compute-dtype) leaf views of the master ``(pq, ps)`` —
    what the FWD/BWD stages consume.  Inverse of :func:`quant_master_pack`
    up to the storage grid's round-off."""
    rows_p, lanes = pq.shape
    n_blocks = ps.shape[0]
    br = rows_p // n_blocks
    pb = (pq.astype(jnp.float32).reshape(n_blocks, br * lanes)
          * ps).reshape(rows_p, lanes)
    return unpack_leaves(pb, shapes, dtypes)


def fused_adamw_update_quant(pq, ps, mb, vb, gb, lr_t, t, *, fmt: str,
                             b1: float, b2: float, eps: float,
                             weight_decay: float,
                             interpret: bool | None = None):
    """One quantized-master AdamW PU step over packed buffers:
    ``(new_pq, new_ps, new_mb, new_vb)``.

    ``pq``/``ps`` from :func:`quant_master_pack`; ``mb``/``vb``/``gb`` are
    (rows_p, LANES) f32 packed moment/grad buffers (``pack_leaves``).  The
    master is dequantized, updated, re-scaled and stochastically re-rounded
    entirely inside the kernel — no dense f32 parameter buffer touches HBM.
    """
    if interpret is None:
        interpret = kernel_interpret_default()
    rows_p, lanes = pq.shape
    n_blocks = ps.shape[0]
    br = rows_p // n_blocks
    grid = (n_blocks,)
    blk = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    sblk = pl.BlockSpec((1, 1), lambda i: (i, 0))
    kern = functools.partial(_adamw_quant_kernel, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay, fmt=fmt)
    out = pl.pallas_call(
        kern,
        name="fused_adamw_quant",
        grid=grid,
        in_specs=[_scal_spec(),
                  blk, sblk, blk, blk, blk],
        out_specs=[blk, sblk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct(pq.shape, pq.dtype),
                   jax.ShapeDtypeStruct(ps.shape, ps.dtype),
                   jax.ShapeDtypeStruct(mb.shape, mb.dtype),
                   jax.ShapeDtypeStruct(vb.shape, vb.dtype)],
        input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3},
        interpret=interpret,
    )(_scal(lr_t, t, b1, b2), pq, ps, mb, vb, gb)
    return tuple(out)


def quant_pu_hbm_bytes(n_params: int, fmt: str) -> int:
    """HBM bytes of one quantized-master AdamW PU step: the packed master
    streams at the storage itemsize (read + aliased write) plus its scale
    sidecar; moments and grads stay f32 as in ``fused_pu_hbm_bytes``."""
    its = _quant.itemsize(fmt)
    br, rows_p, lanes = pu_block_shape(n_params)
    n_pad = rows_p * lanes
    n_blocks = rows_p // br
    reads = n_pad * (its + 4 + 4 * 2) + 4 * n_blocks
    writes = n_pad * (its + 4 * 2) + 4 * n_blocks
    return reads + writes


# ---------------------------------------------------------------------------
# Sketch-compressed AdamW (Count-Sketch Optimizers' fused-kernel idea).
#
# Dense AdamW's two f32 moment buffers are 2x the parameter footprint — the
# dominant PU-stage cost against the paper's on-chip budget.  Following
# "Memory-Constrained Optimization via Count-Sketches", the moments are held
# as d x w hash sketches (w << n_params) and BOTH the sketch refresh and the
# parameter update happen inside one Pallas kernel, so the dense ``m``/``v``
# buffers never exist in HBM:
#
# * second moment ``v`` (nonnegative): a count-MIN sketch with a
#   *conservative* refresh — per step every cell is overwritten with the
#   MAX over its colliding parameters of the decayed estimate
#   ``b2 * est_v + (1 - b2) * g^2``; queries take the MIN over the d rows.
#   By induction the estimate never under-shoots the dense ``v``
#   (the CMS overestimate invariant, asserted elementwise in
#   tests/test_sketched_update.py), so sketching can only *shrink* step
#   sizes — the safe direction for Adam.
# * first moment ``m`` (signed): a count-sketch updated in the LINEAR
#   form — the EMA is linear, so the sketch itself can be the EMA: cells
#   decay by ``b1`` once per step and accumulate only
#   ``sign_r(i) * (1 - b1) * g_i``.  Each cell then holds exactly the
#   signed sum of its colliders' true dense ``m``; queries take the MEDIAN
#   over rows of the sign-corrected cells (the classical unbiased
#   estimator) and collision noise is zero-mean.  Crucially the sketch
#   state never depends on its own queries — rewriting full estimates
#   ``b1 * est_m + (1-b1) g`` into cells instead would feed ~sqrt(#colliders)
#   query noise back through ``b1`` and amplify it exponentially.
#
# Per grid step the kernel hashes the block's flat parameter indices
# (multiplicative hashing, compile-time odd constants — the identical
# functions are exported below so the NumPy oracle in the tests computes
# the very same buckets), queries the previous step's sketches, applies the
# bias-corrected update to the parameter block, and scatters the refreshed
# estimates into the new sketches, which live in VMEM-resident output
# blocks (constant index map) flushed to HBM once per launch.  The gather/
# scatter run as jnp take/segment ops in the kernel body — exact in
# interpret mode (the validation path, as everywhere in this package); the
# native TPU lowering is the one-hot/MXU idiom ``ttm_embed.py`` already
# uses for its gather-free lookup.
# ---------------------------------------------------------------------------

SKETCH_DEPTH_DEFAULT = 3

# Odd multiplicative-hash constants per sketch row (Knuth/Murmur-style).
# Deterministic module-level tables: the kernel, the pure-JAX oracle, and a
# restored checkpoint all hash identically by construction.
_HASH_MULT = 2654435761        # 2^32 / golden ratio, odd
_HASH_ADD = 0x85EBCA77
_SIGN_MULT = 0xC2B2AE3D
_SIGN_ADD = 0x27D4EB2F


def _hash_consts(depth: int, mult: int, add: int):
    ms = [(mult * (2 * r + 3)) & 0xFFFFFFFF | 1 for r in range(depth)]
    bs = [(add * (r + 1)) & 0xFFFFFFFF for r in range(depth)]
    return ms, bs


def sketch_bucket_ids(idx, depth: int, width: int):
    """(depth, *idx.shape) int32 bucket ids in [0, width) for flat parameter
    indices ``idx`` — multiplicative hashing on uint32 with the top
    log2(width) bits.  ``width`` must be a power of two.  This is THE hash
    the kernel uses; the tests' dense NumPy oracle calls it too."""
    if width & (width - 1) or width <= 0:
        raise ValueError(f"sketch width must be a power of two, got {width}")
    shift = 32 - int(math.log2(width))
    u = jnp.asarray(idx).astype(jnp.uint32) + jnp.uint32(1)
    ms, bs = _hash_consts(depth, _HASH_MULT, _HASH_ADD)
    return jnp.stack([
        ((u * jnp.uint32(ms[r]) + jnp.uint32(bs[r]))
         >> jnp.uint32(shift)).astype(jnp.int32)
        for r in range(depth)])


def sketch_signs(idx, depth: int):
    """(depth, *idx.shape) f32 in {-1, +1}: the count-sketch sign hashes for
    the first-moment rows (top bit of an independent multiplicative hash)."""
    u = jnp.asarray(idx).astype(jnp.uint32) + jnp.uint32(1)
    ms, bs = _hash_consts(depth, _SIGN_MULT, _SIGN_ADD)
    return jnp.stack([
        1.0 - 2.0 * ((u * jnp.uint32(ms[r]) + jnp.uint32(bs[r]))
                     >> jnp.uint32(31)).astype(jnp.float32)
        for r in range(depth)])


def default_sketch_width(n_params: int, depth: int = SKETCH_DEPTH_DEFAULT) -> int:
    """Largest power-of-two width with ``depth * width <= n_params / 8``
    (floor 128): both sketches together are then <= 1/8 of ONE dense moment
    buffer, i.e. >= 16x under dense AdamW's two.  Capped so the kernel's six
    resident (depth, width) sketch blocks stay within half the VMEM budget —
    the default width never fails ``sketch_pu_fits`` on VMEM grounds."""
    from .btt_linear import VMEM_BUDGET

    target = max(n_params // (8 * max(depth, 1)), 1)
    cap = max(VMEM_BUDGET // (2 * 6 * max(depth, 1) * 4), 128)
    target = min(target, cap)
    return max(1 << (target.bit_length() - 1), 128)


def sketch_state_bytes(depth: int, width: int) -> int:
    """HBM-persistent optimizer state of the sketched path: two f32
    (depth, width) sketches (vs + ms) — vs dense AdamW's 2 * n_params f32."""
    return 2 * depth * width * 4


def sketch_pu_vmem_bytes(n_params: int, width: int,
                         depth: int = SKETCH_DEPTH_DEFAULT, *,
                         itemsize: int = 4) -> int:
    """VMEM working set of one sketched-update grid step: the param block
    (storage dtype) + grad block (f32) + two f32 index/estimate temporaries,
    plus all six sketch blocks live across the launch (old vs/ms in, seed
    vs/ms in, new vs/ms resident output).  The single residency source for
    the ledger's sketched PU rows (like ``pu_block_shape`` for the dense
    kernel)."""
    br, _, lanes = pu_block_shape(n_params)
    return br * lanes * (itemsize + 4 + 8) + 6 * depth * width * 4


def sketch_pu_fits(n_params: int, width: int,
                   depth: int = SKETCH_DEPTH_DEFAULT, *,
                   itemsize: int = 4) -> bool:
    """The dispatch predicate ``optim.adamw(sketched=True)`` gates on (and
    the memory ledger with it — same function, no drift): the kernel's
    working set must fit the VMEM budget AND the sketch state must be at
    least 4x smaller than the dense moments it replaces (tiny trees fall
    back to dense fused AdamW — a 128-wide sketch saves nothing there)."""
    from .btt_linear import VMEM_BUDGET

    return (sketch_pu_vmem_bytes(n_params, width, depth,
                                 itemsize=itemsize) <= VMEM_BUDGET
            and 4 * sketch_state_bytes(depth, width) <= 2 * n_params * 4)


def _sketched_math(scal_ref, vso_ref, mso_ref, vsd_ref, msd_ref, g_ref,
                   ovs_ref, oms_ref, p, br: int, lanes: int, *,
                   b1: float, b2: float, eps: float, weight_decay: float,
                   depth: int, width: int, n_valid: int, base: int):
    """Shared body of the sketched PU kernels: query the old sketches,
    refresh the new ones, and return the updated flat f32 parameter block.

    ``base`` is the global flat offset of this launch's dtype group and
    ``n_valid`` its true element count; padded lanes hash to masked
    (identity) contributions so they never pollute a bucket.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # Seed the new sketches: zeros for the step's first dtype group,
        # the previous group's partial sketches otherwise.
        ovs_ref[...] = vsd_ref[...]
        oms_ref[...] = msd_ref[...]

    lr = scal_ref[0, 0]
    bc1 = scal_ref[0, 2]
    bc2 = scal_ref[0, 3]
    rows = jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (br, lanes), 1)
    local = (rows * lanes + cols + i * br * lanes).reshape(-1)
    valid = local < n_valid
    idx = local + base
    h = sketch_bucket_ids(idx, depth, width)         # (depth, n_blk)
    s = sketch_signs(idx, depth)
    vs_old = vso_ref[...]
    ms_old = mso_ref[...]
    # Query last step's estimates: min over rows (count-min, v) and median
    # over sign-corrected rows (count-sketch, m).
    est_v = jnp.min(jnp.stack(
        [jnp.take(vs_old[r], h[r]) for r in range(depth)]), axis=0)
    est_m = jnp.sort(jnp.stack(
        [jnp.take(ms_old[r], h[r]) * s[r] for r in range(depth)]),
        axis=0)[(depth - 1) // 2]
    g = g_ref[...].reshape(-1)
    m_new = b1 * est_m + (1.0 - b1) * g
    v_new = b2 * est_v + (1.0 - b2) * jnp.square(g)
    # Refresh the sketches: conservative overwrite (max of decayed
    # estimates) for v, signed accumulation for m; masked elements
    # contribute the scatter identity (0 — v_new >= 0 always).
    v_c = jnp.where(valid, v_new, 0.0)
    zero_w = jnp.zeros((width,), jnp.float32)
    for r in range(depth):
        ovs_ref[r, :] = jnp.maximum(ovs_ref[r, :], zero_w.at[h[r]].max(v_c))
        # linear count-sketch refresh: only the gradient increment — the b1
        # decay of the cells happens once per step in the host-side seed.
        oms_ref[r, :] = oms_ref[r, :] + zero_w.at[h[r]].add(
            jnp.where(valid, s[r] * (1.0 - b1) * g, 0.0))
    step = lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if weight_decay:
        step = step + lr * weight_decay * p
    return p - step


def _sketched_adamw_kernel(scal_ref, p_ref, vso_ref, mso_ref, vsd_ref,
                           msd_ref, g_ref, o_ref, ovs_ref, oms_ref, *,
                           b1: float, b2: float, eps: float,
                           weight_decay: float, depth: int, width: int,
                           n_valid: int, base: int):
    """One (br, lanes) block of the sketched PU stage (f32 master)."""
    br, lanes = p_ref.shape
    p = p_ref[...].astype(jnp.float32).reshape(-1)
    p_new = _sketched_math(
        scal_ref, vso_ref, mso_ref, vsd_ref, msd_ref, g_ref, ovs_ref,
        oms_ref, p, br, lanes, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, depth=depth, width=width,
        n_valid=n_valid, base=base)
    o_ref[...] = p_new.reshape(br, lanes).astype(o_ref.dtype)


def _sketched_adamw_quant_kernel(scal_ref, pq_ref, ps_ref, vso_ref, mso_ref,
                                 vsd_ref, msd_ref, g_ref, oq_ref, ops_ref,
                                 ovs_ref, oms_ref, *, b1: float, b2: float,
                                 eps: float, weight_decay: float, depth: int,
                                 width: int, n_valid: int, base: int,
                                 fmt: str):
    """Sketched PU block with a quantized (int8/fp8) master: in-VMEM
    dequant on entry, in-kernel rescale + stochastic re-round on exit —
    composes the two HBM compressions (sketched moments, quantized
    params) in one kernel pass."""
    br, lanes = pq_ref.shape
    p = (pq_ref[...].astype(jnp.float32) * ps_ref[0, 0]).reshape(-1)
    p_new = _sketched_math(
        scal_ref, vso_ref, mso_ref, vsd_ref, msd_ref, g_ref, ovs_ref,
        oms_ref, p, br, lanes, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, depth=depth, width=width,
        n_valid=n_valid, base=base)
    f = _quant.resolve(fmt)
    s_new = jnp.maximum(jnp.max(jnp.abs(p_new)), _quant._TINY) / f.qmax
    ops_ref[0, 0] = s_new
    oq_ref[...] = _quant.stochastic_round(
        (p_new / s_new).reshape(br, lanes), fmt,
        scal_ref[0, 1].astype(jnp.int32), pl.program_id(0))


def _sketched_call(kern, scal, pb, gb, vs_old, ms_old, vs_seed, ms_seed,
                   br: int, interpret: bool):
    """Launch the sketched kernel over one packed dtype group.  The param
    buffer is aliased in place; the (depth, width) sketch blocks have a
    constant index map — VMEM-resident across the (sequential) grid,
    flushed to HBM once, exactly like btt_backward's gA/gB accumulators."""
    rows_p, lanes = pb.shape
    grid = (rows_p // br,)
    blk = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    skb = pl.BlockSpec(vs_old.shape, lambda i: (0, 0))
    out = pl.pallas_call(
        kern,
        name="fused_adamw_sketched",
        grid=grid,
        in_specs=[_scal_spec(),
                  blk, skb, skb, skb, skb, blk],
        out_specs=[blk, skb, skb],
        out_shape=[jax.ShapeDtypeStruct(pb.shape, pb.dtype),
                   jax.ShapeDtypeStruct(vs_old.shape, vs_old.dtype),
                   jax.ShapeDtypeStruct(ms_old.shape, ms_old.dtype)],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal, pb, vs_old, ms_old, vs_seed, ms_seed, gb)
    return tuple(out)


def sketched_adamw_update(params, grads, vs, ms, lr_t, t, *, b1: float,
                          b2: float, eps: float, weight_decay: float,
                          interpret: bool | None = None):
    """One sketched-AdamW PU stage: ``(new_params, new_vs, new_ms)``.

    ``vs``/``ms`` are the (depth, width) f32 count-min / count-sketch
    moment sketches from the previous step (zeros at step 0 — matching
    dense AdamW's zero-initialized moments).  Per dtype group one kernel
    launch queries the old sketches, updates the parameters, and scatters
    the refreshed estimates into the new ones; groups chain through the
    seed operands so the final sketches cover the whole tree.  Flat
    parameter indices are global across the concatenated group layout, so
    the hash assignment is stable across steps and checkpoints.
    """
    if interpret is None:
        interpret = kernel_interpret_default()
    depth, width = vs.shape
    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    new_p: list = [None] * len(p_leaves)
    scal = _scal(lr_t, t, b1, b2)
    kern = functools.partial(
        _sketched_adamw_kernel, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, depth=depth, width=width)
    vs_seed = jnp.zeros_like(vs)
    # m-sketch EMA decay is applied ONCE per step here; kernels then only
    # scatter-add the (1 - b1)-scaled signed gradient increments.
    ms_seed = b1 * ms
    base = 0
    for idx in _dtype_groups(p_leaves):
        group = [p_leaves[i] for i in idx]
        n = sum(int(np.prod(x.shape)) for x in group)
        br, rows_p, lanes = pu_block_shape(n)
        pdt = group[0].dtype
        pb = pack_leaves(group, pdt, rows_p, lanes)
        gb = pack_leaves([g_leaves[i] for i in idx], jnp.float32, rows_p,
                         lanes)
        ob, vs_seed, ms_seed = _sketched_call(
            functools.partial(kern, n_valid=n, base=base),
            scal, pb, gb, vs, ms, vs_seed, ms_seed, br, interpret)
        outs = unpack_leaves(ob, [x.shape for x in group],
                             [pdt] * len(group))
        for j, i in enumerate(idx):
            new_p[i] = outs[j]
        base += n
    return jax.tree.unflatten(treedef, new_p), vs_seed, ms_seed


def sketched_adamw_update_quant(pq, ps, vs, ms, gb, n_valid: int, lr_t, t,
                                *, fmt: str, b1: float, b2: float,
                                eps: float, weight_decay: float,
                                interpret: bool | None = None):
    """Sketched-AdamW PU step over a quantized packed master:
    ``(new_pq, new_ps, new_vs, new_ms)``.

    The quantized master is a single packed buffer (``quant_master_pack``),
    so unlike :func:`sketched_adamw_update` there is exactly one launch
    (``base = 0``); ``n_valid`` is the true (unpadded) element count and
    ``gb`` the (rows_p, LANES) f32 packed gradient buffer.
    """
    if interpret is None:
        interpret = kernel_interpret_default()
    depth, width = vs.shape
    rows_p, lanes = pq.shape
    n_blocks = ps.shape[0]
    br = rows_p // n_blocks
    kern = functools.partial(
        _sketched_adamw_quant_kernel, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay, depth=depth, width=width,
        n_valid=n_valid, base=0, fmt=fmt)
    blk = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    sblk = pl.BlockSpec((1, 1), lambda i: (i, 0))
    skb = pl.BlockSpec(vs.shape, lambda i: (0, 0))
    out = pl.pallas_call(
        kern,
        name="fused_adamw_sketched_quant",
        grid=(n_blocks,),
        in_specs=[_scal_spec(),
                  blk, sblk, skb, skb, skb, skb, blk],
        out_specs=[blk, sblk, skb, skb],
        out_shape=[jax.ShapeDtypeStruct(pq.shape, pq.dtype),
                   jax.ShapeDtypeStruct(ps.shape, ps.dtype),
                   jax.ShapeDtypeStruct(vs.shape, vs.dtype),
                   jax.ShapeDtypeStruct(ms.shape, ms.dtype)],
        input_output_aliases={1: 0, 2: 1},
        # seed sketches (zeros / b1-decayed) ride as the vsd/msd operands.
        interpret=interpret,
    )(_scal(lr_t, t, b1, b2), pq, ps, vs, ms, jnp.zeros_like(vs),
      b1 * ms, gb)
    return tuple(out)


# ---------------------------------------------------------------------------
# Analytic HBM-traffic models (shared by benchmarks and the run.py --check
# regression guard).
# ---------------------------------------------------------------------------


def _moment_buffers(optimizer: str, momentum: float = 0.0) -> int:
    if optimizer == "adamw":
        return 2
    return 1 if momentum else 0


def _tile_padded_elems(shape: tuple, itemsize: int) -> int:
    """HBM footprint of one leaf stored alone: XLA pads a TPU array's
    minor two dims to the dtype's (sublane, 128) tile.  1-D leaves are
    modeled lane-padded only — generous to the unfused side."""
    if len(shape) == 0:
        return 1
    if len(shape) == 1:
        return _round_up(int(shape[0]), 128)
    sub = _cm_sublane(itemsize)  # f32 8, bf16 16, int8 32 (shared source)
    lead = 1
    for d in shape[:-2]:
        lead *= int(d)
    return lead * _round_up(int(shape[-2]), sub) * _round_up(int(shape[-1]),
                                                             128)


def fused_pu_hbm_bytes(leaves, optimizer: str, *,
                       momentum: float = 0.0) -> int:
    """HBM bytes of one fused PU step over ``leaves`` (arrays or
    ShapeDtypeStructs): per dtype group, every packed buffer (params,
    grads f32, moments f32) is read once and the param/moment buffers
    written once through ``input_output_aliases`` — the dense flat packing
    is the paper's grouped BRAM storage (Eqs. (24)/(25)): <1 block of
    padding per group instead of per-leaf tile waste."""
    n_m = _moment_buffers(optimizer, momentum)
    groups: dict = {}
    for x in leaves:
        dt = jnp.dtype(x.dtype)
        groups.setdefault(dt, 0)
        groups[dt] += int(np.prod(x.shape))
    total = 0
    for dt, n in groups.items():
        _, rows_p, lanes = pu_block_shape(n)
        n_pad = rows_p * lanes
        reads = n_pad * (dt.itemsize + 4 + 4 * n_m)
        writes = n_pad * (dt.itemsize + 4 * n_m)
        total += reads + writes
    return total


def unfused_pu_hbm_bytes(leaves, optimizer: str, *,
                         momentum: float = 0.0) -> int:
    """HBM bytes of the per-leaf XLA update: the same read/write counts as
    the fused model (generous — perfect elementwise fusion, each buffer
    touched once), but every leaf at its OWN tile-padded footprint: TT
    cores are tiny, so storing them alone wastes most of each (8, 128)
    tile (the waste ``core.cost_model.tpu_packing_efficiency`` measures
    and the packed layout exists to eliminate)."""
    n_m = _moment_buffers(optimizer, momentum)
    total = 0
    for x in leaves:
        its = jnp.dtype(x.dtype).itemsize
        n_pad = _tile_padded_elems(tuple(x.shape), its)
        n_pad_f32 = _tile_padded_elems(tuple(x.shape), 4)
        reads = n_pad * its + n_pad_f32 * (4 + 4 * n_m)
        writes = n_pad * its + n_pad_f32 * 4 * n_m
        total += reads + writes
    return total

def sketched_pu_hbm_bytes(leaves, *, depth: int = SKETCH_DEPTH_DEFAULT,
                          width: int | None = None) -> int:
    """HBM bytes of one *sketched* AdamW PU step: per dtype group the packed
    params (read + aliased write) and f32 grads (read) stream once, and per
    launch the four (depth, width) sketch operands (old vs/ms + seed vs/ms)
    are read and the two new ones written — the dense moment traffic
    (8 bytes/elem read + 8 written in ``fused_pu_hbm_bytes``) is gone
    entirely, replaced by O(depth * width) per launch."""
    groups: dict = {}
    for x in leaves:
        dt = jnp.dtype(x.dtype)
        groups.setdefault(dt, 0)
        groups[dt] += int(np.prod(x.shape))
    if width is None:
        width = default_sketch_width(sum(groups.values()), depth)
    total = 0
    for dt, n in groups.items():
        _, rows_p, lanes = pu_block_shape(n)
        n_pad = rows_p * lanes
        total += n_pad * (dt.itemsize + 4)      # read params + grads
        total += n_pad * dt.itemsize            # write params
        total += 6 * depth * width * 4          # 4 sketch reads + 2 writes
    return total
