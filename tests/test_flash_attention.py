"""Flash-attention Pallas kernel vs the naive softmax oracle (interpret)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_pallas


def naive(q, k, v, causal, window, group):
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


CASES = [
    # (BH_kv, group, S, D, causal, window, tq, tk, hb)
    (2, 1, 256, 64, True, None, 128, 128, 1),
    (2, 4, 256, 64, True, None, 128, 128, 1),      # GQA
    (1, 2, 300, 80, True, None, 128, 128, 1),      # ragged S and D
    (2, 1, 256, 64, False, None, 128, 128, 1),     # encoder (non-causal)
    (2, 2, 512, 64, True, 128, 128, 128, 1),       # sliding window
    (1, 1, 256, 128, True, None, 256, 128, 1),     # asymmetric tiles
    # Head blocks: hb whole (batch·head) rows a grid step.
    (12, 1, 32, 64, False, None, None, None, 12),  # ATIS at batch 1
    (48, 1, 32, 64, False, None, None, None, 16),  # ATIS at batch 4
    (4, 4, 128, 64, True, None, None, None, 8),    # causal GQA
    (2, 2, 100, 80, True, 32, None, None, 4),      # window, ragged S, D
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_naive(case, dtype):
    bh_kv, group, S, D, causal, window, tq, tk, hb = case
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(sum(case[:4])), 3)
    q = jax.random.normal(kq, (bh_kv * group, S, D), dtype)
    k = jax.random.normal(kk, (bh_kv, S, D), dtype)
    v = jax.random.normal(kv_, (bh_kv, S, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 group=group, tq=tq, tk=tk, hb=hb,
                                 interpret=True)
    ref = naive(q, k, v, causal, window, group)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_blockwise_model_layer():
    """Cross-check against the model's blockwise attention (B,S,H,D layout)."""
    from repro.models.attention import blockwise_attention
    B, S, H, KV, D = 2, 256, 8, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, D))
    ref = blockwise_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    out = flash_attention_pallas(qf, kf, vf, causal=True, group=H // KV,
                                 tq=128, tk=128, interpret=True)
    out = out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", [c for c in CASES
                                  if c[8] > 1 and c[2] % 32 == 0])
def test_head_block_matches_one_head_a_step(case):
    """A head block issues each head's products as the one-tile launch
    does: where that launch pads no key (S a multiple of 32), o and the
    statistics (m, l) are bit-identical to one (batch·head) pair a grid
    step with tq = tk = S."""
    bh_kv, group, S, D, causal, window, _, _, hb = case
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(sum(case[:4])), 3)
    q = jax.random.normal(kq, (bh_kv * group, S, D))
    k = jax.random.normal(kk, (bh_kv, S, D))
    v = jax.random.normal(kv_, (bh_kv, S, D))
    kw = dict(causal=causal, window=window, group=group, interpret=True,
              return_residuals=True)
    got = flash_attention_pallas(q, k, v, hb=hb, **kw)
    want = flash_attention_pallas(q, k, v, tq=S, tk=S, **kw)
    for name, u, w in zip(("o", "m", "l"), got, want):
        assert u.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(u), np.asarray(w),
                                      err_msg=name)
