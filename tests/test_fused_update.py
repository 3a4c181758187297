"""Fused PU-stage kernels (kernels.fused_update) + the memory ledger.

The fused path must be a drop-in for the pure-JAX optimizers: same state
layout, same numerics within fp32 tolerance — including momentum and AdamW
bias correction compounding over multiple steps.  Verified over the real
ATIS TT parameter tree (TT cores, TTM embedding cores, biases, norms), in
interpret mode as with every kernel test here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.atis_transformer import config_n
from repro.core.cost_model import mem_btt
from repro.core.memory_ledger import (
    BRAM_BUDGET_BYTES,
    URAM_BUDGET_BYTES,
    budget_report,
    training_step_ledger,
)
from repro.core import make_tt_spec
from repro.kernels.fused_update import (
    pack_leaves,
    pu_block_shape,
    unpack_leaves,
)
from repro.models import init_params, num_params
from repro.optim import adamw, sgd

N_STEPS = 4


@pytest.fixture(scope="module")
def tt_params():
    """The paper's 2-encoder ATIS model: TT cores + TTM cores + biases."""
    return init_params(jax.random.PRNGKey(0), config_n(2))


def _fake_grads(params, seed):
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        0.1 * jax.random.normal(k, x.shape, jnp.float32)
        for k, x in zip(keys, leaves)])


def _run_steps(opt, params, n_steps):
    state = opt.init(params)
    upd = jax.jit(lambda g, p, s: opt.update(g, p, s, s["step"]))
    for i in range(n_steps):
        params, state = upd(_fake_grads(params, i), params, state)
    return params, state


def _assert_tree_close(a, b, rtol=2e-6, atol=2e-7):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_fused_sgd_matches_unfused_over_steps(tt_params, momentum):
    p_ref, s_ref = _run_steps(sgd(4e-3, momentum), tt_params, N_STEPS)
    p_fus, s_fus = _run_steps(
        sgd(4e-3, momentum, fused=True, interpret=True), tt_params, N_STEPS)
    _assert_tree_close(p_ref, p_fus)
    if momentum:
        _assert_tree_close(s_ref["mu"], s_fus["mu"])
    assert int(s_fus["step"]) == N_STEPS


def test_fused_adamw_matches_unfused_over_steps(tt_params):
    """Moment EMAs + bias correction (SMEM scalars) + weight decay, compounded
    over N steps, must track the pure-JAX path."""
    mk = lambda fused: adamw(1e-3, b1=0.9, b2=0.95, eps=1e-8,
                             weight_decay=0.01, fused=fused,
                             interpret=True if fused else None)
    p_ref, s_ref = _run_steps(mk(False), tt_params, N_STEPS)
    p_fus, s_fus = _run_steps(mk(True), tt_params, N_STEPS)
    _assert_tree_close(p_ref, p_fus)
    _assert_tree_close(s_ref["m"], s_fus["m"], rtol=1e-5, atol=1e-7)
    _assert_tree_close(s_ref["v"], s_fus["v"], rtol=1e-5, atol=1e-9)


def test_fused_sgd_schedule_lr(tt_params):
    """Traced (scheduled) learning rates flow through the SMEM scalars."""
    from repro.optim import warmup_cosine
    lr = warmup_cosine(1e-2, 2, 10)
    p_ref, _ = _run_steps(sgd(lr), tt_params, 3)
    p_fus, _ = _run_steps(sgd(lr, fused=True, interpret=True), tt_params, 3)
    _assert_tree_close(p_ref, p_fus)


def test_fused_mixed_dtype_groups():
    """bf16 params + f32 params in one tree: one kernel launch per group."""
    params = {
        "w16": jnp.ones((96, 40), jnp.bfloat16),
        "w32": jnp.ones((300,), jnp.float32),
    }
    grads = jax.tree.map(lambda p: jnp.full(p.shape, 0.5, jnp.float32), params)
    new = sgd(0.1, fused=True, interpret=True).update(
        grads, params, {"step": jnp.zeros((), jnp.int32)},
        jnp.zeros((), jnp.int32))[0]
    assert new["w16"].dtype == jnp.bfloat16
    assert new["w32"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(new["w32"]), 0.95, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new["w16"], np.float32), 0.95,
                               rtol=1e-2)


def test_pack_unpack_roundtrip():
    shapes = [(12, 8, 12), (1, 8, 12), (300,), (768, 12)]
    leaves = [jax.random.normal(jax.random.PRNGKey(i), s) for i, s in
              enumerate(shapes)]
    n = sum(int(np.prod(s)) for s in shapes)
    br, rows_p, lanes = pu_block_shape(n)
    assert rows_p % br == 0 and rows_p * lanes >= n
    buf = pack_leaves(leaves, jnp.float32, rows_p, lanes)
    back = unpack_leaves(buf, shapes, [jnp.float32] * len(shapes))
    for x, y in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(seed=st.integers(0, 10_000),
       sizes=st.lists(st.integers(1, 400), min_size=1, max_size=8),
       nd=st.integers(1, 3))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip_ragged_property(seed, sizes, nd):
    """Any ragged list of leaf sizes survives pack -> unpack exactly, and
    the padding tail of the packed buffer is zero (the scatter-identity the
    sketched kernel's mask relies on)."""
    rng = np.random.default_rng(seed)
    shapes = []
    for n in sizes:
        if nd == 1 or n < 4:
            shapes.append((n,))
        else:
            d0 = max(int(rng.integers(1, n)), 1)
            shapes.append((d0, -(-n // d0)))  # >= n elems, 2-D
    leaves = [jnp.asarray(rng.normal(size=s), jnp.float32) for s in shapes]
    total = sum(int(np.prod(s)) for s in shapes)
    br, rows_p, lanes = pu_block_shape(total)
    assert rows_p % br == 0 and rows_p * lanes >= total
    buf = pack_leaves(leaves, jnp.float32, rows_p, lanes)
    assert buf.shape == (rows_p, lanes)
    back = unpack_leaves(buf, shapes, [jnp.float32] * len(shapes))
    for x, y in zip(leaves, back):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    flat = np.asarray(buf).reshape(-1)
    np.testing.assert_array_equal(flat[total:], 0.0)


@given(seed=st.integers(0, 10_000), n16=st.integers(1, 300),
       n32=st.integers(1, 300), n8=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_pack_unpack_mixed_dtype_groups_property(seed, n16, n32, n8):
    """_dtype_groups partitions leaves by dtype preserving order; packing
    each group at its own dtype and unpacking restores every leaf exactly
    (bf16/f32 exact since values are stored at their own precision)."""
    from repro.kernels.fused_update import _dtype_groups

    rng = np.random.default_rng(seed)
    leaves = [
        jnp.asarray(rng.normal(size=n32), jnp.float32),
        jnp.asarray(rng.normal(size=n16), jnp.float32).astype(jnp.bfloat16),
        jnp.asarray(rng.normal(size=max(n32 // 2, 1)), jnp.float32),
    ]
    if n8:
        leaves.append(jnp.asarray(rng.integers(-100, 100, size=n8),
                                  jnp.int8))
    groups = _dtype_groups(leaves)
    # every leaf appears in exactly one group, order preserved within
    flat_idx = [i for g in groups for i in g]
    assert sorted(flat_idx) == list(range(len(leaves)))
    for idx in groups:
        dts = {leaves[i].dtype for i in idx}
        assert len(dts) == 1
        assert list(idx) == sorted(idx)
        group = [leaves[i] for i in idx]
        dt = group[0].dtype
        total = sum(int(np.prod(x.shape)) for x in group)
        _, rows_p, lanes = pu_block_shape(total)
        buf = pack_leaves(group, dt, rows_p, lanes)
        back = unpack_leaves(buf, [x.shape for x in group],
                             [dt] * len(group))
        for x, y in zip(group, back):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pack_unpack_empty_leaf_edge():
    """Zero-size leaves pack to nothing and unpack to their own (empty)
    shape without disturbing their neighbours."""
    shapes = [(7,), (0,), (3, 5), (2, 0, 4)]
    leaves = [jnp.asarray(np.arange(int(np.prod(s))).reshape(s),
                          jnp.float32) for s in shapes]
    total = sum(int(np.prod(s)) for s in shapes)
    _, rows_p, lanes = pu_block_shape(max(total, 1))
    buf = pack_leaves(leaves, jnp.float32, rows_p, lanes)
    back = unpack_leaves(buf, shapes, [jnp.float32] * len(shapes))
    for x, y in zip(leaves, back):
        assert y.shape == x.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Memory ledger vs the cost model, on the paper's config.
# ---------------------------------------------------------------------------

# The paper's layer (Table II): 768x768, d=3, rank 12, uniform ranks —
# built through the model's own factorization path (factorize orders the
# factors (12, 8, 8), a permutation of the paper's printed (8, 8, 12)).
PAPER_SPEC = make_tt_spec(768, 768, 3, 12, clamp_ranks=False)
K_PAPER = 32  # batch 1 x seq 32


@pytest.fixture(scope="module")
def atis_ledger():
    return training_step_ledger(config_n(2), "sgd", batch=1, seq=32)


def test_ledger_tt_intermediates_match_cost_model(atis_ledger):
    """The FWD/BWD intermediate entry is exactly Eq. (21) on the paper's
    768x768 rank-12 layer (the largest TT layer in the ATIS model)."""
    expect = mem_btt(PAPER_SPEC, K_PAPER) * 4  # f32
    assert atis_ledger["FWD"].entry("tt_intermediates").nbytes == expect
    assert atis_ledger["BWD"].entry("tt_intermediates").nbytes == expect


def test_ledger_param_and_grad_totals(atis_ledger, tt_params):
    """params entry == eval_shape-exact bytes == the real initialized tree;
    grads entry == one f32 per parameter."""
    n = num_params(tt_params)
    assert atis_ledger["PU"].entry("params").nbytes == n * 4  # fp32 model
    assert atis_ledger["BWD"].entry("grads").nbytes == n * 4
    # SGD without momentum keeps no moments.
    assert atis_ledger["PU"].entry("moments").nbytes == 0


def test_ledger_adamw_moments(tt_params):
    led = training_step_ledger(config_n(2), "adamw")
    assert led["PU"].entry("moments").nbytes == num_params(tt_params) * 2 * 4


def test_ledger_fits_paper_envelope(atis_ledger):
    """The paper's central claim, checked in software: every stage of the
    ATIS training step fits the 6 MB BRAM + 22.5 MB URAM envelope."""
    rep = budget_report(atis_ledger)
    assert rep["fits_bram"] and rep["fits_uram"] and rep["fits"]
    assert rep["bram_peak_bytes"] <= BRAM_BUDGET_BYTES
    assert rep["uram_peak_bytes"] <= URAM_BUDGET_BYTES
    # ... and the 6-encoder model still fits (paper Table IV runs it).
    rep6 = budget_report(training_step_ledger(config_n(6), "sgd"))
    assert rep6["fits"]


def test_ledger_matrix_model_busts_budget():
    """Sanity inversion: the uncompressed (matrix) model must NOT fit —
    otherwise the ledger isn't measuring anything."""
    rep = budget_report(training_step_ledger(config_n(2, tt_mode="off"),
                                             "sgd"))
    assert not rep["fits_bram"]
