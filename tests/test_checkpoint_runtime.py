"""Checkpointing (atomicity, keep-k, async, integrity/CRC, corrupt-step
fallback) + runtime (sharding rules, straggler monitor, EF compression)."""
import json
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.checkpoint import (
    CheckpointCorruptError,
    CheckpointManager,
    latest_step,
    list_steps,
    restore,
    restore_latest_valid,
    save,
    verify_step,
)
from repro.configs import SHAPES, get_config
from repro.core import tt_linear_init
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_inputs
from repro.models import init_params
from repro.runtime import (
    CheckpointCadence,
    StragglerMonitor,
    batch_specs,
    cache_specs,
    dequantize_int8,
    ef_compress_tree,
    ef_init,
    kv_repeat_for_mesh,
    param_specs,
    quantize_int8,
)


def _tree(seed=0):
    return {
        "lin": tt_linear_init(jax.random.PRNGKey(seed), 128, 128, d=2, rank=4),
        "emb": {"table": jax.random.normal(jax.random.PRNGKey(seed + 1), (64, 16))},
        "step": jnp.asarray(41),
    }


def _template(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t)
    restored, step = restore(str(tmp_path), _template(t))
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_checkpoint_keep_k_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30, 40):
        mgr.save_async(s, _tree())
    mgr.wait()
    assert list_steps(str(tmp_path)) == [30, 40]
    assert latest_step(str(tmp_path)) == 40
    _, step = mgr.restore_latest(_template(_tree()))
    assert step == 40


def test_checkpoint_atomicity_partial_dir_ignored(tmp_path):
    """A crash mid-save (stray tmp dir, no manifest entry) must not corrupt
    restore."""
    t = _tree()
    save(str(tmp_path), 5, t)
    # simulate a crashed writer: partial temp dir + orphan step dir
    os.makedirs(tmp_path / ".tmp_save_crash")
    (tmp_path / ".tmp_save_crash" / "leaf_00000.npy").write_bytes(b"garbage")
    os.makedirs(tmp_path / "step_00000099")  # no meta.json, not in manifest
    restored, step = restore(str(tmp_path), _template(t))
    assert step == 5


def test_checkpoint_shape_mismatch_raises(tmp_path):
    t = _tree()
    save(str(tmp_path), 1, t)
    bad = _template(t)
    bad["emb"]["table"] = jax.ShapeDtypeStruct((65, 16), jnp.float32)
    with pytest.raises(ValueError):
        restore(str(tmp_path), bad)


def test_checkpoint_manifest_is_json(tmp_path):
    save(str(tmp_path), 3, _tree())
    m = json.load(open(tmp_path / "manifest.json"))
    assert m["latest"] == 3


def test_checkpoint_fused_sketched_opt_state_roundtrip(tmp_path):
    """Fused-optimizer state including the sketch buffers survives a
    checkpoint round-trip, and a restored run continues BIT-identically —
    the hash families are module-level constants, so bucket assignment is
    stable across processes and the sketches resume exactly."""
    from repro.optim import adamw

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=30_000), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(40, 12)), jnp.float32)}
    grads = [jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), jnp.float32), params)
        for _ in range(4)]
    opt = adamw(1e-3, weight_decay=0.01, sketched=True)
    state = opt.init(params)
    assert "vs" in state  # sketch engaged: buffers are part of the state

    # two steps, checkpoint, two more
    for g in grads[:2]:
        params, state = opt.update(g, params, state, state["step"])
    save(str(tmp_path), 2, (params, state))
    for g in grads[2:]:
        params, state = opt.update(g, params, state, state["step"])

    # restore mid-run and replay the same two steps
    (rp, rs), step = restore(str(tmp_path), _template((params, state)))
    assert step == 2
    rp = jax.tree.map(jnp.asarray, rp)
    rs = jax.tree.map(jnp.asarray, rs)
    assert rs["vs"].shape == state["vs"].shape
    for g in grads[2:]:
        rp, rs = opt.update(g, rp, rs, rs["step"])
    for a, b in zip(jax.tree.leaves((params, state)),
                    jax.tree.leaves((rp, rs))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Integrity: per-leaf CRC, corrupt-step fallback, async-writer failures.
# ---------------------------------------------------------------------------


def test_crc_recorded_and_verified(tmp_path):
    t = _tree()
    save(str(tmp_path), 4, t)
    meta = json.load(open(tmp_path / "step_00000004" / "meta.json"))
    assert all("crc32" in rec for rec in meta["leaves"])
    assert verify_step(str(tmp_path), 4)


@pytest.mark.parametrize("mode", ["flip", "truncate", "delete", "meta"])
def test_corrupt_step_restore_raises(tmp_path, mode):
    """Any corruption of the newest step must surface as an exception on
    direct restore — never as silently wrong weights."""
    from repro.runtime.chaos import corrupt_checkpoint

    t = _tree()
    save(str(tmp_path), 4, t)
    corrupt_checkpoint(str(tmp_path), 4, mode=mode, seed=1)
    assert not verify_step(str(tmp_path), 4)
    with pytest.raises((CheckpointCorruptError, ValueError, OSError,
                        KeyError, EOFError, FileNotFoundError)):
        restore(str(tmp_path), _template(t))


def test_flip_corruption_is_crc_not_shape(tmp_path):
    """A bit flip inside leaf DATA keeps shape/dtype valid — only the CRC
    catches it, and it reports as CheckpointCorruptError specifically."""
    t = _tree()
    save(str(tmp_path), 4, t)
    # corrupt a byte well past the .npy header, inside the payload
    step_dir = tmp_path / "step_00000004"
    leaf = sorted(f for f in os.listdir(step_dir)
                  if f.startswith("leaf_"))[0]
    path = step_dir / leaf
    data = bytearray(path.read_bytes())
    data[-1] ^= 0xFF
    path.write_bytes(bytes(data))
    with pytest.raises(CheckpointCorruptError):
        restore(str(tmp_path), _template(t))


def test_restore_latest_valid_falls_back_and_repairs(tmp_path):
    from repro.runtime.chaos import corrupt_checkpoint

    trees = {s: _tree(seed=s) for s in (1, 2, 3)}
    for s, t in trees.items():
        save(str(tmp_path), s, t)
    corrupt_checkpoint(str(tmp_path), 3, mode="truncate", seed=0)
    got = restore_latest_valid(str(tmp_path), _template(trees[1]))
    assert got is not None
    (restored, step), skipped = got
    assert step == 2 and skipped == [3]
    for a, b in zip(jax.tree.leaves(trees[2]), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # repaired: the bad step is pruned from manifest AND disk, so plain
    # restore now works without the fallback
    assert list_steps(str(tmp_path)) == [1, 2]
    assert not (tmp_path / "step_00000003").exists()
    _, step = restore(str(tmp_path), _template(trees[1]))
    assert step == 2


def test_restore_latest_valid_all_corrupt_returns_none(tmp_path):
    from repro.runtime.chaos import corrupt_checkpoint

    t = _tree()
    save(str(tmp_path), 1, t)
    corrupt_checkpoint(str(tmp_path), 1, mode="delete", seed=0)
    assert restore_latest_valid(str(tmp_path), _template(t)) is None
    # nothing valid found -> nothing repaired/deleted (wrong-template
    # safety: a bad template must not nuke good checkpoints)
    assert list_steps(str(tmp_path)) == [1]


def test_async_writer_failure_reraised_by_wait(tmp_path):
    """Satellite fix: a background-save exception must re-raise from
    wait(), not vanish into the thread — and the crashed save must leave
    no step directory (atomicity)."""
    from repro.runtime.chaos import WriterCrash, async_writer_crash

    mgr = CheckpointManager(str(tmp_path), keep=3)
    t = _tree()
    mgr.save_async(1, t)
    mgr.wait()
    with async_writer_crash(after_leaves=2):
        mgr.save_async(2, t)
        with pytest.raises(RuntimeError, match="step 2"):
            mgr.wait()
    assert list_steps(str(tmp_path)) == [1]
    assert not any(d.startswith(".tmp_save") for d in os.listdir(tmp_path))
    # the cause chain names the real failure
    try:
        with async_writer_crash():
            mgr.save_async(3, t)
            mgr.wait()
    except RuntimeError as e:
        assert isinstance(e.__cause__, WriterCrash)
    else:
        raise AssertionError("wait() swallowed the writer crash")
    # the manager recovers: a later save works
    mgr.save_async(4, t)
    mgr.wait()
    assert latest_step(str(tmp_path)) == 4


def test_manager_restore_latest_valid_skips_corrupt(tmp_path):
    from repro.runtime.chaos import corrupt_checkpoint

    mgr = CheckpointManager(str(tmp_path), keep=3)
    for s in (1, 2):
        mgr.save_async(s, _tree(seed=s))
    mgr.wait()
    corrupt_checkpoint(str(tmp_path), 2, mode="flip", seed=5)
    got = mgr.restore_latest_valid(_template(_tree()))
    assert got is not None and got[1] == 1


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16),
       mode=st.sampled_from(["flip", "truncate", "delete", "meta"]),
       data=st.data())
def test_property_corruption_never_loses_the_run(seed, mode, data):
    """PROPERTY: whatever byte of whichever leaf of the newest checkpoint
    is flipped/truncated/deleted, ``restore_latest_valid`` returns the
    earlier intact step BIT-identically and never raises.  (Fresh tmpdir
    per example — pytest's tmp_path is per-test, not per-example.)"""
    from repro.runtime.chaos import corrupt_checkpoint

    root = tempfile.mkdtemp(prefix="ckpt_prop_")
    try:
        good = _tree(seed=7)
        save(root, 5, good)
        save(root, 9, _tree(seed=8))
        n_leaves = len(jax.tree.leaves(good))
        leaf = (data.draw(st.integers(0, n_leaves - 1))
                if mode in ("flip", "truncate", "delete") else None)
        corrupt_checkpoint(root, 9, leaf=leaf, mode=mode, seed=seed)
        got = restore_latest_valid(root, _template(good))
        assert got is not None
        (restored, step), skipped = got
        assert step == 5 and skipped == [9]
        for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Sharding rules (single-device mesh: specs must still be derivable).
# ---------------------------------------------------------------------------


def _leaf_specs(tree):
    return jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["qwen3-8b", "llama4-maverick-400b-a17b",
                                  "mamba2-130m", "recurrentgemma-2b"])
def test_param_specs_cover_every_leaf(arch):
    cfg = get_config(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(cfg, params, mesh)
    p_leaves = jax.tree.leaves(params)
    s_leaves = _leaf_specs(specs)
    assert len(p_leaves) == len(s_leaves)
    for leaf, spec in zip(p_leaves, s_leaves):
        assert len(tuple(spec)) <= len(leaf.shape)


def test_param_specs_tt_cores_replicated():
    cfg = get_config("qwen3-8b").with_tt(mode="tt")
    mesh = make_mesh((1, 1), ("data", "model"))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    specs = param_specs(cfg, params, mesh)
    sflat = _leaf_specs(specs)
    for (path, leaf), spec in zip(flat, sflat):
        if ".cores[" in jax.tree_util.keystr(path) or "cores" in str(path):
            assert tuple(spec) == () or all(s is None for s in tuple(spec)), \
                f"TT core {jax.tree_util.keystr(path)} not replicated: {spec}"


def test_batch_and_cache_specs():
    cfg = get_config("llama3-8b")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = SHAPES["decode_32k"]
    kvr = kv_repeat_for_mesh(cfg, mesh)
    inputs = make_inputs(cfg, shape, kv_repeat=kvr)
    cs = cache_specs(cfg, mesh, shape.global_batch, shape.seq_len)
    # structurally compatible with the cache inputs
    jax.tree.map(lambda leaf, spec: None, inputs["cache"], cs)
    bs = batch_specs({"tokens": inputs["tokens"]}, mesh)
    assert isinstance(bs["tokens"], P)


def test_kv_repeat_rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert kv_repeat_for_mesh(get_config("llama3-8b"), mesh) >= 1
    # 16-way TP mesh requires fake devices; the divisor logic is pure:
    from repro.runtime.sharding import kv_repeat_for_mesh as f
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    assert f(get_config("llama3-8b"), FakeMesh()) == 2       # kv8 x2 = 16
    assert f(get_config("recurrentgemma-2b"), FakeMesh()) == 1  # 10 heads
    assert f(get_config("qwen3-8b"), FakeMesh()) == 2        # kv8 group4


# ---------------------------------------------------------------------------
# Straggler monitor + cadence.
# ---------------------------------------------------------------------------


def test_straggler_flags_injected_delay():
    m = StragglerMonitor()
    rng = np.random.default_rng(0)
    for _ in range(50):
        assert not m.observe(0.1 + 0.002 * rng.random())
    assert m.observe(0.5)            # 5x spike -> flagged
    assert not m.persistent
    m.observe(0.5)
    m.observe(0.5)
    assert m.persistent              # 3 consecutive -> escalated


def test_straggler_stats_robust_to_outliers():
    m = StragglerMonitor()
    for _ in range(30):
        m.observe(0.1)
    m.observe(10.0)                  # outlier must not poison the baseline
    assert m.mean < 0.2


def test_cadence_shrinks_under_instability():
    mon = StragglerMonitor()
    cad = CheckpointCadence(base_interval=1000, min_interval=50)
    for _ in range(30):
        mon.observe(0.1)
    healthy = cad.interval(mon)
    mon.persistent = True
    assert cad.interval(mon) == 50 < healthy


# ---------------------------------------------------------------------------
# int8 gradient compression + error feedback.
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,))
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) / 2 + 1e-6
    assert q.dtype == jnp.int8


def test_error_feedback_unbiased_accumulation():
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (256,)) * 1e-3}
    r = ef_init(g)
    acc = jnp.zeros(256)
    n = 100
    for _ in range(n):
        qg, r = ef_compress_tree(g, r)
        acc = acc + qg["w"]
    rel = float(jnp.abs(acc - n * g["w"]).max() / jnp.abs(n * g["w"]).max())
    assert rel < 5e-3  # EF keeps the long-run average unbiased
