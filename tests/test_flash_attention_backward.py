"""Fused flash-attention backward (kernels.flash_backward) — gradient-oracle
harness, mirroring test_btt_backward's three layers of ground truth:

1. ``flash_attention_bwd_ref`` — the simplest per-head expression of the
   same contractions (P recomputed from the saved (m, l); D = rowsum(dO⊙O)
   as the kernel computes it).  The kernel must match it bit-for-bit on
   unpadded single-tile shapes (identical dot_generals in identical
   accumulation order) and to f32 tolerance elsewhere.
2. Autodiff through dense softmax — ``jax.vjp`` of the naive S×S attention.
   Parametrized over causal / sliding-window / GQA / ragged shapes, plus
   hypothesis property tests sampling the same axes.
3. The op level (``flash_mha_op``): gradient parity with autodiff through
   ``blockwise_attention``, the VMEM-budget fallback (bitwise-identical to
   the blockwise path when the budget gate trips), and the analytic
   HBM-traffic acceptance: the fused path moves strictly fewer bytes than
   the blockwise path on every shipped ATIS config.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels import (
    attn_bwd_vmem_fits,
    choose_attn_tiles,
    flash_attention_bwd_pallas,
    flash_attention_bwd_ref,
    flash_attention_pallas,
    flash_mha_op,
    fused_attn_hbm_bytes,
    unfused_attn_hbm_bytes,
)
from repro.kernels.btt_linear import VMEM_BUDGET
from repro.models.attention import blockwise_attention


def naive_attention(q, k, v, causal, window, group):
    """Dense softmax attention, (BH, S, D) layout — the autodiff oracle."""
    BH, S, D = q.shape
    kr = jnp.repeat(k, group, axis=0)
    vr = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) / math.sqrt(D)
    idx = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= idx[None, :] <= idx[:, None]
    if window is not None:
        mask &= idx[None, :] > idx[:, None] - window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, vr.astype(jnp.float32)).astype(q.dtype)


def _operands(bh_kv, group, S, D, dtype=jnp.float32, seed=None):
    ks = jax.random.split(
        jax.random.PRNGKey(seed if seed is not None else bh_kv + group + S + D), 4)
    q = jax.random.normal(ks[0], (bh_kv * group, S, D), dtype)
    k = jax.random.normal(ks[1], (bh_kv, S, D), dtype)
    v = jax.random.normal(ks[2], (bh_kv, S, D), dtype)
    do = jax.random.normal(ks[3], (bh_kv * group, S, D), dtype)
    return q, k, v, do


def _kernel_grads(q, k, v, do, causal, window, group, tq=None, tk=None,
                  hb=1):
    o, m, l = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     group=group, tq=tq, tk=tk, hb=hb,
                                     interpret=True, return_residuals=True)
    return flash_attention_bwd_pallas(q, k, v, o, m, l, do, causal=causal,
                                      window=window, group=group, tq=tq,
                                      tk=tk, hb=hb, interpret=True)


def _oracle_grads(q, k, v, do, causal, window, group):
    _, vjp = jax.vjp(
        lambda a, b, c: naive_attention(a, b, c, causal, window, group),
        q, k, v)
    return vjp(do)


def _assert_close(got, want, tol, names=("dq", "dk", "dv")):
    """Scale-relative comparison (see test_btt_backward for rationale)."""
    for name, u, v in zip(names, got, want):
        u = np.asarray(u, np.float32)
        v = np.asarray(v, np.float32)
        scale = max(float(np.max(np.abs(v))), 1e-6)
        np.testing.assert_allclose(u / scale, v / scale, rtol=0, atol=tol,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# Kernel vs autodiff through dense softmax.
# ---------------------------------------------------------------------------

CASES = [
    # (BH_kv, group, S, D, causal, window, hb)
    (2, 1, 256, 64, True, None, 1),
    (2, 4, 256, 64, True, None, 1),      # GQA
    (1, 2, 300, 80, True, None, 1),      # ragged S and D
    (2, 1, 256, 64, False, None, 1),     # encoder (non-causal; the ATIS model)
    (2, 2, 512, 64, True, 128, 1),       # sliding window
    (1, 1, 32, 64, False, None, 1),      # the paper's S=32 regime, unpadded
    # Head blocks: hb whole (batch·head) rows a grid step.
    (12, 1, 32, 64, False, None, 12),    # ATIS at batch 1: one step
    (48, 1, 32, 64, False, None, 16),    # ATIS at batch 4
    (4, 4, 128, 64, True, None, 8),      # causal GQA: dK/dV summed in-step
    (2, 2, 100, 80, True, 32, 4),        # window, ragged S and D
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bwd_kernel_matches_dense_autodiff(case, dtype):
    bh_kv, group, S, D, causal, window, hb = case
    q, k, v, do = _operands(bh_kv, group, S, D, dtype)
    got = _kernel_grads(q, k, v, do, causal, window, group, hb=hb)
    want = _oracle_grads(q, k, v, do, causal, window, group)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    _assert_close(got, want, tol)


# ---------------------------------------------------------------------------
# Bit-equality vs the reference on unpadded single-tile shapes.
# ---------------------------------------------------------------------------

SINGLE_TILE = [
    # (BH_kv, group, S, causal, window, hb) — D = 128, tq = tk = S: no
    # padding, one grid step per (head, q-block), identical GEMMs in
    # identical order.  hb > 1: a head block of whole sequences, the same
    # GEMMs per head and dK/dV summed over the group ascending.
    (2, 2, 256, True, None, 1),
    (1, 1, 128, False, None, 1),
    (2, 1, 32, True, None, 1),
    (2, 1, 32, False, None, 1),
    (1, 1, 256, True, 64, 1),
    (12, 1, 32, False, None, 12),
    (48, 1, 32, False, None, 16),
    (4, 4, 128, True, None, 8),
]


@pytest.mark.parametrize("case", SINGLE_TILE)
def test_bwd_kernel_bitmatches_ref_single_tile(case):
    """One grid step per (head, q-block) => the kernel issues the
    reference's exact GEMMs in the reference's accumulation order; results
    must be bit-identical (both paths fed the same forward (o, m, l))."""
    bh_kv, group, S, causal, window, hb = case
    q, k, v, do = _operands(bh_kv, group, S, 128)
    o, m, l = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     group=group, tq=S, tk=S, interpret=True,
                                     return_residuals=True)
    got = flash_attention_bwd_pallas(q, k, v, o, m, l, do, causal=causal,
                                     window=window, group=group, tq=S, tk=S,
                                     hb=hb, interpret=True)
    want = flash_attention_bwd_ref(q, k, v, o, m, l, do, causal=causal,
                                   window=window, group=group)
    for name, u, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(w),
                                      err_msg=name)


@pytest.mark.parametrize("case", CASES[:3])
def test_bwd_kernel_close_to_ref_multi_tile(case):
    """Tiled launches reorder the f32 accumulations; the kernel must still
    track the reference to tolerance on padded/multi-tile shapes."""
    bh_kv, group, S, D, causal, window, _ = case
    q, k, v, do = _operands(bh_kv, group, S, D)
    o, m, l = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     group=group, tq=128, tk=128,
                                     interpret=True, return_residuals=True)
    got = flash_attention_bwd_pallas(q, k, v, o, m, l, do, causal=causal,
                                     window=window, group=group, tq=128,
                                     tk=128, interpret=True)
    want = flash_attention_bwd_ref(q, k, v, o, m, l, do, causal=causal,
                                   window=window, group=group)
    _assert_close(got, want, 1e-5)


# ---------------------------------------------------------------------------
# Hypothesis property tests: causal/window/GQA/ragged-S sweep at op level.
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 2),
    kv=st.integers(1, 2),
    group=st.sampled_from([1, 2, 4]),
    s=st.integers(4, 130),
    d=st.sampled_from([16, 64, 80]),
    causal=st.booleans(),
    windowed=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_op_grads_match_dense_autodiff_oracle(b, kv, group, s, d, causal,
                                              windowed, seed):
    """Property: over sampled (B, KV, group, ragged S, D, causal, window),
    jax.grad through flash_mha_op tracks autodiff through dense softmax."""
    window = max(s // 2, 1) if windowed else None
    H = kv * group
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, H, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    do = jax.random.normal(ks[3], (b, s, H, d))

    def fused(q_, k_, v_):
        out = flash_mha_op(q_, k_, v_, causal=causal, window=window,
                           interpret=True)
        return (out * do).sum()

    def oracle(q_, k_, v_):
        qf = q_.transpose(0, 2, 1, 3).reshape(b * H, s, d)
        kf = k_.transpose(0, 2, 1, 3).reshape(b * kv, s, d)
        vf = v_.transpose(0, 2, 1, 3).reshape(b * kv, s, d)
        out = naive_attention(qf, kf, vf, causal, window, group)
        out = out.reshape(b, H, s, d).transpose(0, 2, 1, 3)
        return (out * do).sum()

    got = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    _assert_close(got, want, 2e-5)


# ---------------------------------------------------------------------------
# VMEM-budget fallback parity.
# ---------------------------------------------------------------------------


def test_op_fallback_when_budget_exceeded():
    """With a tiny budget the op must silently take the blockwise path —
    bitwise-identical gradients to calling blockwise_attention directly —
    and the grads must still match the dense oracle."""
    B, S, H, KV, D = 1, 96, 4, 2, 32
    assert not attn_bwd_vmem_fits(S, D, 4, budget=1)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))

    def loss_fb(q_, k_, v_):
        return (flash_mha_op(q_, k_, v_, causal=True, q_chunk=32,
                             kv_chunk=32, budget=1) ** 2).sum()

    def loss_bw(q_, k_, v_):
        return (blockwise_attention(q_, k_, v_, causal=True, q_chunk=32,
                                    kv_chunk=32) ** 2).sum()

    g_fb = jax.grad(loss_fb, argnums=(0, 1, 2))(q, k, v)
    g_bw = jax.grad(loss_bw, argnums=(0, 1, 2))(q, k, v)
    for u, w in zip(jax.tree.leaves(g_fb), jax.tree.leaves(g_bw)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(w))

    def oracle(q_, k_, v_):
        qf = q_.transpose(0, 2, 1, 3).reshape(B * H, S, D)
        kf = k_.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
        vf = v_.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
        out = naive_attention(qf, kf, vf, True, None, H // KV)
        return (out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
                .astype(q_.dtype) ** 2).sum()

    g_or = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    _assert_close(g_fb, g_or, 2e-5)


def test_long_sequences_exceed_real_budget():
    """The real budget gate: decode/prefill-scale sequences (dK/dV residency
    grows with S) must route to the blockwise path."""
    assert not attn_bwd_vmem_fits(32768, 128, 2)
    assert attn_bwd_vmem_fits(32, 64, 4)          # the paper's regime fits


@pytest.mark.parametrize("S,D,itemsize,rows,group,hb", [
    (32, 64, 4, 512 * 12, 1, 32),     # ATIS b512s32: 192 steps, not 6,144
    (32, 64, 4, 12, 1, 12),           # ATIS b1s32: all 12 heads, one step
    (32, 64, 4, 4 * 12, 1, 24),
    (128, 64, 4, 16, 4, 8),           # GQA: a multiple of the group
    (32, 64, 4, 1, 1, 1),             # one head: nothing to block
])
def test_chooser_takes_a_head_block_where_the_sequence_is_one_tile(
        S, D, itemsize, rows, group, hb):
    got, tq, tk, sp, _, vmem = choose_attn_tiles(S, D, itemsize, rows=rows,
                                                 group=group)
    assert (got, tq, tk, sp) == (hb, S, S, S)   # nq = nk = 1
    assert rows % got == 0 and got % group == 0
    assert vmem <= VMEM_BUDGET


@pytest.mark.parametrize("S,rows,group", [
    (512, 8 * 32, 4),                 # Granite b8s512
    (4096, 32, 4),                    # Granite b1s4096
])
def test_chooser_keeps_one_head_a_step_for_long_sequences(S, rows, group):
    """Granite's shapes: today's (256, 256) tiles, one (batch, head) pair
    a grid step."""
    got = choose_attn_tiles(S, 128, 2, rows=rows, group=group)
    assert got[:3] == (1, 256, 256)
    assert got == (1, *choose_attn_tiles(S, 128, 2)[1:])


# ---------------------------------------------------------------------------
# Model-level threading: fused_attn flag end to end.
# ---------------------------------------------------------------------------


def test_model_grads_match_with_fused_attn():
    """loss_fn grads with cfg.fused_attn on vs off (ATIS encoder: the
    non-causal paper model) — the flag must be numerics-preserving."""
    from repro.configs.atis_transformer import config_n
    from repro.models import init_params, loss_fn

    cfg = config_n(2).scaled_down(d_model=128, n_heads=4, d_ff=128,
                                  vocab_size=1000, num_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    l0, g0 = jax.value_and_grad(
        lambda p: loss_fn(p, cfg, batch, remat=False))(params)
    l1, g1 = jax.value_and_grad(
        lambda p: loss_fn(p, cfg.with_fused_attn(True), batch,
                          remat=False))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Analytic HBM traffic: fused must move strictly fewer bytes (acceptance).
# ---------------------------------------------------------------------------


def test_fused_moves_fewer_hbm_bytes_for_shipped_configs():
    """For every shipped ATIS config's attention shape (and a larger
    GQA shape), the fused fwd+bwd launch pair's analytic HBM traffic is
    strictly below the blockwise+autodiff path's."""
    from repro.configs.atis_transformer import config_n

    for n_enc in (2, 4, 6):
        cfg = config_n(n_enc)
        its = jnp.dtype(cfg.dtype).itemsize
        fused = fused_attn_hbm_bytes(1, cfg.n_heads, cfg.n_kv_heads, 32,
                                     cfg.d_head, its, causal=cfg.causal)
        unfused = unfused_attn_hbm_bytes(1, cfg.n_heads, cfg.n_kv_heads, 32,
                                         cfg.d_head, its,
                                         q_chunk=cfg.attn_q_chunk,
                                         kv_chunk=cfg.attn_kv_chunk)
        assert fused < unfused, (n_enc, fused, unfused)
    # At context scale the S×S probability term keeps the blockwise path
    # >1.5x the fused traffic (the fused side's own K/V refetch per Q block
    # bounds the asymptotic ratio near tq/dp — it does not grow unboundedly).
    for S in (256, 1024, 4096):
        fused = fused_attn_hbm_bytes(1, 8, 2, S, 128, 2)
        unfused = unfused_attn_hbm_bytes(1, 8, 2, S, 128, 2)
        assert unfused > 1.5 * fused, (S, fused, unfused)
