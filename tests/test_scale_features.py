"""Scale-oriented features added during §Perf iterations: mesh-context
activation constraints, MoE expert padding, TTM strategy crossover."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.meshctx import activation_mesh, constrain, current_mesh
from repro.core.ttm_embedding import (
    make_ttm_spec,
    ttm_embedding_apply,
    ttm_embedding_init,
    ttm_strategy_crossover,
)
from repro.launch.mesh import make_mesh
from repro.models.moe import moe_apply, moe_init


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    assert current_mesh() is None
    y = constrain(x, "model", None)
    np.testing.assert_array_equal(x, y)


def test_constrain_applies_and_degrades():
    mesh = make_mesh((1, 1), ("data", "model"))
    with activation_mesh(mesh):
        assert current_mesh() is mesh
        x = jnp.ones((4, 8))
        # divisible dims -> constraint applied (values unchanged)
        y = constrain(x, "data", "model")
        np.testing.assert_array_equal(x, y)
        # unknown axis name and non-divisible dims degrade silently
        z = constrain(jnp.ones((3, 5)), "expert", ("data", "model"))
        assert z.shape == (3, 5)
    assert current_mesh() is None


def test_constrain_inside_jit():
    mesh = make_mesh((1,), ("model",))

    def f(x):
        return constrain(x * 2, "model") + 1

    with activation_mesh(mesh):
        out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(out, jnp.arange(4.0) * 2 + 1)


# ---------------------------------------------------------------------------
# MoE expert padding.
# ---------------------------------------------------------------------------


def test_expert_padding_shapes_and_routing():
    cfg = get_config("qwen2-moe-a2.7b").scaled_down()
    m = dataclasses.replace(cfg.moe, num_experts=6, pad_experts_to=8,
                            capacity_factor=8.0)
    cfg = dataclasses.replace(cfg, moe=m)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    assert p["up"]["w"].shape[0] == 8          # padded expert stack
    assert p["router"].shape[0] == 6           # router covers real experts
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y = moe_apply(p, x, cfg)
    assert np.isfinite(np.asarray(y)).all()
    # dummy experts receive zero gradient (never routed to)
    g = jax.grad(lambda pp: (moe_apply(pp, x, cfg) ** 2).sum())(p)
    dummy_grad = np.abs(np.asarray(g["up"]["w"][6:])).max()
    assert dummy_grad == 0.0


def test_expert_padding_matches_unpadded_math():
    cfg = get_config("qwen2-moe-a2.7b").scaled_down()
    m0 = dataclasses.replace(cfg.moe, num_experts=6, pad_experts_to=None,
                             capacity_factor=8.0)
    m1 = dataclasses.replace(m0, pad_experts_to=8)
    c0 = dataclasses.replace(cfg, moe=m0)
    c1 = dataclasses.replace(cfg, moe=m1)
    p0 = moe_init(jax.random.PRNGKey(0), c0)
    p1 = moe_init(jax.random.PRNGKey(0), c1)
    # copy the real experts so both models share weights
    for k in ("up", "gate", "down"):
        p1[k]["w"] = p1[k]["w"].at[:6].set(p0[k]["w"])
    p1["router"] = p0["router"]
    p1["shared"] = p0["shared"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    np.testing.assert_allclose(moe_apply(p0, x, c0), moe_apply(p1, x, c1),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# TTM strategy crossover.
# ---------------------------------------------------------------------------


def test_ttm_strategies_agree():
    emb = ttm_embedding_init(jax.random.PRNGKey(0), 1000, 256, d=3, rank=16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 1000)
    a = ttm_embedding_apply(emb, ids, strategy="gather")
    b = ttm_embedding_apply(emb, ids, strategy="reconstruct")
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_ttm_crossover_scales_with_table():
    small = make_ttm_spec(1000, 256, 3, 16)
    big = make_ttm_spec(131072, 4096, 3, 64)
    assert ttm_strategy_crossover(big) > ttm_strategy_crossover(small)
    # the auto rule: decode-sized batches gather, training-sized reconstruct
    assert ttm_strategy_crossover(big) > 128          # decode stays gather
    assert ttm_strategy_crossover(big) < 256 * 4096   # train reconstructs


@pytest.mark.parametrize("n_ids", [4, 50_000])
def test_ttm_auto_strategy_is_consistent(n_ids):
    emb = ttm_embedding_init(jax.random.PRNGKey(0), 512, 64, d=2, rank=4)
    ids = jax.random.randint(jax.random.PRNGKey(1), (n_ids,), 0, 512)
    out = ttm_embedding_apply(emb, ids)  # auto
    ref = ttm_embedding_apply(emb, ids[:16], strategy="gather")
    np.testing.assert_allclose(out[:16], ref, rtol=2e-4, atol=1e-5)
