"""Ahead-of-time compiles of the training-path Pallas kernels for a TPU v5e.

No chip is needed: the TPU compiler is installed with ``jaxlib`` and
compiles for a described (not attached) v5e.  Each test lowers one kernel
with ``interpret=False`` at the paper's ATIS widths (d_model 768, TT rank
12, 12 heads of 64, K = batch * seq = 32 and 4096 rows; flash attention
also over 512 sequences in head blocks) and asserts that
Mosaic produced a ``tpu_custom_call``: a kernel that breaks the TPU block
rules or cannot be lowered fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a pytest-xdist worker
that is not given this file must not try.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.btt_backward import btt_backward_pallas
from repro.kernels.btt_ffn import btt_ffn_bwd_pallas, btt_ffn_pallas
from repro.kernels.btt_linear import btt_linear_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_backward import (
    choose_attn_tiles,
    flash_attention_bwd_pallas,
)
from repro.kernels.fused_update import fused_adamw_update, fused_sgd_update

D_MODEL, RANK, D_FF = 768, 12, 768
HEADS, D_HEAD, SEQ = 12, 64, 32
# ~ the 2-encoder ATIS TT model's trainable element count.
N_PARAMS = 400_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("K", [32, 4096])
def test_btt_linear_compiles(one_chip, K):
    _assert_mosaic(
        lambda x, b, a: btt_linear_pallas(x, b, a, interpret=False),
        _spec(one_chip, (K, D_MODEL)), _spec(one_chip, (RANK, D_MODEL)),
        _spec(one_chip, (D_MODEL, RANK)))


@pytest.mark.parametrize("K", [32, 4096])
def test_btt_backward_compiles(one_chip, K):
    _assert_mosaic(
        lambda x, gy, b, a: btt_backward_pallas(x, gy, b, a,
                                                interpret=False),
        _spec(one_chip, (K, D_MODEL)), _spec(one_chip, (K, D_MODEL)),
        _spec(one_chip, (RANK, D_MODEL)), _spec(one_chip, (D_MODEL, RANK)))


def _ffn_factors(sh):
    return (_spec(sh, (RANK, D_MODEL)), _spec(sh, (D_FF, RANK)),
            _spec(sh, (RANK, D_FF)), _spec(sh, (D_MODEL, RANK)))


@pytest.mark.parametrize("K", [32, 4096])
def test_btt_ffn_forward_compiles(one_chip, K):
    _assert_mosaic(
        lambda x, b1, a1, b2, a2: btt_ffn_pallas(x, b1, a1, b2, a2,
                                                 act="gelu",
                                                 interpret=False),
        _spec(one_chip, (K, D_MODEL)), *_ffn_factors(one_chip))


@pytest.mark.parametrize("K", [32, 4096])
def test_btt_ffn_backward_compiles(one_chip, K):
    _assert_mosaic(
        lambda x, gy, b1, a1, b2, a2: btt_ffn_bwd_pallas(
            x, gy, b1, a1, b2, a2, act="gelu", interpret=False),
        _spec(one_chip, (K, D_MODEL)), _spec(one_chip, (K, D_MODEL)),
        *_ffn_factors(one_chip))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_with_residuals_compiles(one_chip, causal):
    qkv = [_spec(one_chip, (HEADS, SEQ, D_HEAD)) for _ in range(3)]
    _assert_mosaic(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=causal, tq=SEQ, tk=SEQ, interpret=False,
            return_residuals=True),
        *qkv)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_compiles(one_chip, causal):
    big = [_spec(one_chip, (HEADS, SEQ, D_HEAD)) for _ in range(4)]
    stats = [_spec(one_chip, (HEADS, SEQ)) for _ in range(2)]
    q, k, v, o = big
    _assert_mosaic(
        lambda q, k, v, o, m, l, do: flash_attention_bwd_pallas(
            q, k, v, o, m, l, do, causal=causal, interpret=False),
        q, k, v, o, *stats, _spec(one_chip, (HEADS, SEQ, D_HEAD)))


# The b512s32 cell's attention: 512 sequences x 12 heads, each whole
# sequence one tile, so the chooser gives a head block.
ROWS = 512 * HEADS


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_head_block_compiles(one_chip, causal):
    hb = choose_attn_tiles(SEQ, D_HEAD, 4, rows=ROWS)[0]
    assert hb > 1
    qkv = [_spec(one_chip, (ROWS, SEQ, D_HEAD)) for _ in range(3)]
    _assert_mosaic(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=causal, hb=hb, interpret=False,
            return_residuals=True),
        *qkv)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_head_block_compiles(one_chip, causal):
    hb = choose_attn_tiles(SEQ, D_HEAD, 4, rows=ROWS)[0]
    assert hb > 1
    big = [_spec(one_chip, (ROWS, SEQ, D_HEAD)) for _ in range(4)]
    stats = [_spec(one_chip, (ROWS, SEQ)) for _ in range(2)]
    _assert_mosaic(
        lambda q, k, v, o, m, l, do: flash_attention_bwd_pallas(
            q, k, v, o, m, l, do, causal=causal, hb=hb, interpret=False),
        *big, *stats, _spec(one_chip, (ROWS, SEQ, D_HEAD)))


def _pu_tree(sh):
    return {"w": _spec(sh, (N_PARAMS,))}


def test_fused_sgd_compiles(one_chip):
    _assert_mosaic(
        lambda p, g, lr: fused_sgd_update(p, g, lr, interpret=False),
        _pu_tree(one_chip), _pu_tree(one_chip), _spec(one_chip, ()))


def test_fused_adamw_compiles(one_chip):
    _assert_mosaic(
        lambda p, g, m, v, lr, t: fused_adamw_update(
            p, g, m, v, lr, t, b1=0.9, b2=0.999, eps=1e-8,
            weight_decay=0.01, interpret=False),
        *[_pu_tree(one_chip) for _ in range(4)], _spec(one_chip, ()),
        _spec(one_chip, ()))
