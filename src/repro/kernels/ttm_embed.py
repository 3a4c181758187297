"""Pallas TPU kernel: TTM embedding lookup (d = 3), gather-free.

The paper's TTM embedding (Sec. III-C) looks up one slice per core per token
and chain-multiplies.  Row gathers are the natural FPGA dataflow but are slow
on TPU; the TPU-native adaptation replaces every gather with a **one-hot
matmul** (MXU-friendly — vocab factors are small, tens of rows), and fuses
the whole d=3 chain in VMEM so no per-token slice ever reaches HBM:

  stage A (MXU): sel1 = onehot(j1) @ F1            (TK, H1·R1)
  stage B (MXU): sel2 = onehot(j2) @ F2'           (TK, R1·H2·R2)
  stage C (VPU): acc  = sum_r1 sel1 ⊙ sel2         (TK, H1·H2, R2)
  stage D (MXU): sel3 = onehot(j3) @ F3'           (TK, R2·H3)
  stage E (VPU): out  = sum_r2 acc ⊙ sel3          (TK, H1·H2·H3)

Stages C/E are rank-contractions batched per token — they cannot be a single
2-D GEMM, so they run as broadcast-multiply-reduce on the VPU (tiny:
``r^2·H`` FLOPs/token).  All three cores stay VMEM-resident for the whole
call — the paper's "all parameters on chip" at kernel granularity.  The
wrapper (``ops.py``) falls back to the pure-JAX path when the cores exceed
the VMEM budget (very large vocab × rank).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ttm_embed_pallas", "DEFAULT_TOKENS_BLOCK"]

DEFAULT_TOKENS_BLOCK = 128


def _embed_kernel(oh1_ref, oh2_ref, oh3_ref, f1_ref, f2_ref, f3_ref, out_ref,
                  *, h1: int, h2: int, h3: int, r1: int, r2: int):
    tk = oh1_ref.shape[0]
    f32 = jnp.float32
    # A: (TK, V1) @ (V1, H1*R1)
    sel1 = jnp.dot(oh1_ref[...], f1_ref[...], preferred_element_type=f32)
    # B: (TK, V2) @ (V2, R1*H2*R2)
    sel2 = jnp.dot(oh2_ref[...], f2_ref[...], preferred_element_type=f32)
    # C: contract r1 per token (VPU broadcast-reduce).
    s1 = sel1.reshape(tk, h1, r1, 1, 1)
    s2 = sel2.reshape(tk, 1, r1, h2, r2)
    acc = jnp.sum(s1 * s2, axis=2)                 # (TK, H1, H2, R2)
    # D: (TK, V3) @ (V3, R2*H3)
    sel3 = jnp.dot(oh3_ref[...], f3_ref[...], preferred_element_type=f32)
    # E: contract r2 per token.
    a = acc.reshape(tk, h1 * h2, 1, r2, 1)
    s3 = sel3.reshape(tk, 1, 1, r2, h3)
    out = jnp.sum(a * s3, axis=3)                  # (TK, H1*H2, 1, H3)
    out_ref[...] = out.reshape(tk, h1 * h2 * h3).astype(out_ref.dtype)


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("spec_dims", "tk", "interpret"))
def ttm_embed_pallas(oh: tuple[jax.Array, jax.Array, jax.Array],
                     cores: tuple[jax.Array, jax.Array, jax.Array], *,
                     spec_dims: tuple, tk: int | None = None,
                     interpret: bool = False) -> jax.Array:
    """d=3 TTM lookup.  ``oh[k] (K, v_k)`` one-hot digits (f32/bf16),
    ``cores`` = (F1 (1,v1,h1,r1), F2 (r1,v2,h2,r2), F3 (r2,v3,h3,1)).
    Returns ``(K, h1*h2*h3)``; ``spec_dims = ((v1,v2,v3),(h1,h2,h3),(r1,r2))``.
    """
    (v1, v2, v3), (h1, h2, h3), (r1, r2) = spec_dims
    K = oh[0].shape[0]
    dtype = cores[0].dtype
    tk = tk or DEFAULT_TOKENS_BLOCK
    kp = _round_up(K, tk)
    H = h1 * h2 * h3

    # Flatten cores to 2-D GEMM operands (selection axis first).
    f1 = cores[0].reshape(v1, h1 * r1)
    f2 = jnp.transpose(cores[1], (1, 0, 2, 3)).reshape(v2, r1 * h2 * r2)
    f3 = jnp.transpose(cores[2], (1, 0, 2, 3)).reshape(v3, r2 * h3)

    ohp = [jnp.pad(o, ((0, kp - K), (0, 0))).astype(dtype) for o in oh]

    grid = (kp // tk,)
    out = pl.pallas_call(
        functools.partial(_embed_kernel, h1=h1, h2=h2, h3=h3, r1=r1, r2=r2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tk, v1), lambda k: (k, 0)),
            pl.BlockSpec((tk, v2), lambda k: (k, 0)),
            pl.BlockSpec((tk, v3), lambda k: (k, 0)),
            pl.BlockSpec((v1, h1 * r1), lambda k: (0, 0)),       # resident
            pl.BlockSpec((v2, r1 * h2 * r2), lambda k: (0, 0)),  # resident
            pl.BlockSpec((v3, r2 * h3), lambda k: (0, 0)),       # resident
        ],
        out_specs=pl.BlockSpec((tk, H), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, H), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*ohp, f1, f2, f3)
    return out[:K]
