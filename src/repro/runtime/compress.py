"""int8 gradient compression with error feedback for the DP all-reduce.

The paper's TT compression already shrinks the DP gradient all-reduce by the
model compression ratio (30-52x) — this module stacks a further ~4x on the
*wire format*: a manual ring all-reduce (shard_map + ppermute) whose chunks
travel as int8 (value) + f32 (per-chunk scale), with f32 local accumulation
and error-feedback residuals so quantization noise does not bias SGD.

Why a manual ring: ``jax.lax.psum`` fixes the wire dtype to the operand
dtype, and int8 psum would overflow.  The ring moves int8 on the wire and
accumulates in f32 locally — the standard deep-gradient-compression layout,
expressed with jax-native collectives (ppermute), not emulated NCCL.

``compressed_allreduce_mean(x, axis)`` is a drop-in for
``lax.pmean(x, axis)`` inside shard_map.  Error feedback state is carried by
the caller (one residual tree, same shapes as grads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "quantize_int8", "dequantize_int8",
    "compressed_allreduce_mean", "ef_compress_tree", "ef_init",
]


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: returns (q int8, scale f32)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_allreduce_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Ring all-reduce with int8 wire format.  Call inside shard_map.

    Each device quantizes its own contribution ONCE; every one of the n-1
    ring steps forwards the received ``(q int8, scale)`` chunk VERBATIM one
    hop and accumulates its dequantization locally in f32.  A contribution
    crossing k hops is therefore quantized exactly once, so the per-element
    error of the mean is bounded by ``max_j scale_j / 2`` *independent of
    ring size n* (asserted in tests/test_pipeline.py).  Re-quantizing the
    dequantized receive at each hop — the previous scheme — compounds error
    with n, and the EF residuals (``ef_compress_tree``) only ever see the
    first quantization, so the compounding would go uncompensated.
    Bytes on wire per element per step: 1 (plus one f32 scale per tensor).
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    perm = [(i, (i + 1) % n) for i in range(n)]
    q0, s0 = quantize_int8(x)

    def body(i, carry):
        acc, q, s = carry
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        return acc + dequantize_int8(q, s), q, s

    # The local contribution enters acc unquantized (it never crosses the
    # wire); only remote chunks pay the one int8 round trip.
    acc, _, _ = jax.lax.fori_loop(0, n - 1, body,
                                  (x.astype(jnp.float32), q0, s0))
    return (acc / n).astype(x.dtype)


def ef_init(grads) -> dict:
    """Zero error-feedback residuals, one per gradient leaf."""
    return jax.tree.map(lambda g: jnp.zeros_like(g, dtype=jnp.float32), grads)


def ef_compress_tree(grads, residuals):
    """Error-feedback quantization of a gradient tree.

    Returns (quantized_dequantized_grads, new_residuals): the compensated
    gradient ``g + r`` is quantized; the quantization error becomes the next
    residual, so the *accumulated* update is unbiased (EF-SGD).
    """
    def one(g, r):
        comp = g.astype(jnp.float32) + r
        q, s = quantize_int8(comp)
        deq = dequantize_int8(q, s)
        return deq.astype(g.dtype), comp - deq

    # map twice rather than unzip: structural tuples in real grad trees
    # (e.g. empty tail tuples) would defeat an is_leaf tuple test, and XLA
    # CSEs the duplicated quantize ops anyway.
    new_g = jax.tree.map(lambda g, r: one(g, r)[0], grads, residuals)
    new_r = jax.tree.map(lambda g, r: one(g, r)[1], grads, residuals)
    return new_g, new_r
