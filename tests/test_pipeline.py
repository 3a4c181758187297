"""Mesh-aware fused training: GPipe pipeline + row-TP shard_map (8 devices).

Acceptance gates for the distributed substrate:
  * the 2-stage x 2-DP x 2-TP pipeline reproduces the single-device loss
    per step (f32 tolerance) with the fused bwd/attn/ffn kernels ACTIVE
    (dispatch predicates observed via trace-time counters);
  * the int8 ring all-reduce error bound is independent of ring size;
  * microbatch accumulation is exact under ragged masks;
  * both step builders report a real grad_norm with clipping off;
  * per-device ledger rows reuse the kernels' own tile choosers at the
    pipeline's local K and the ATIS 2/4/6-encoder configs fit the paper's
    6 MB BRAM + 22.5 MB URAM envelope per device.

Multi-device tests fork a subprocess so XLA_FLAGS lands before jax imports
(same idiom as tests/test_ddp_compress.py).
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_child(code: str) -> dict:
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": SRC},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT "):])


PIPELINE_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import repro.kernels.ops as ops

# Trace-time dispatch counters: the predicates (ffn_vmem_fits etc.) choose
# the path while tracing, so wrapping the pallas entry points counts how
# often the FUSED branch was actually taken inside the jitted steps.
counts = {}
def wrap(name):
    orig = getattr(ops, name)
    def counting(*a, **k):
        counts[name] = counts.get(name, 0) + 1
        return orig(*a, **k)
    setattr(ops, name, counting)
for n in ("btt_ffn_pallas", "btt_ffn_bwd_pallas", "flash_attention_pallas",
          "flash_attention_bwd_pallas", "btt_backward_pallas"):
    wrap(n)

from repro.configs.atis_transformer import config_n
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_pipeline_train_step, make_train_step
from repro.models.transformer import init_params
from repro.optim import sgd

cfg = (config_n(2, tt_mode="tt")
       .scaled_down(d_model=256, n_heads=4, d_ff=256, vocab_size=1000,
                    num_layers=2, max_seq_len=64)
       .with_tt(flow="kernel").with_fused_attn(True).with_fused_ffn(True))
B, S, M = 8, 32, 2
params = init_params(jax.random.PRNGKey(0), cfg)
opt = sgd(1e-2, 0.0)
state = opt.init(params)

mesh = make_host_mesh(2, 2, stage=2)
pipe = make_pipeline_train_step(cfg, opt, mesh, microbatches=M)
single = jax.jit(make_train_step(cfg, opt))

def batch_at(i):
    k = jax.random.PRNGKey(100 + i)
    toks = jax.random.randint(k, (B, S), 0, cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.fold_in(k, 1), (B, S)) > 0.2
            ).astype(jnp.float32)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1),
            "mask": mask}

# copy BEFORE the donating pipeline step consumes the originals
p2 = jax.tree.map(jnp.copy, params)
s2 = jax.tree.map(jnp.copy, state)
p1, s1 = params, state
b = batch_at(0)
p1, s1, m1 = pipe(p1, s1, b)
pipe_counts = dict(counts)  # only the pipeline step has traced so far

pairs = [[float(m1["loss"]), None, float(m1["grad_norm"]), None]]
p2, s2, m2 = single(p2, s2, b)
pairs[0][1] = float(m2["loss"]); pairs[0][3] = float(m2["grad_norm"])
for i in range(1, 5):
    b = batch_at(i)
    p1, s1, m1 = pipe(p1, s1, b)
    p2, s2, m2 = single(p2, s2, b)
    pairs.append([float(m1["loss"]), float(m2["loss"]),
                  float(m1["grad_norm"]), float(m2["grad_norm"])])
print("RESULT", json.dumps({"pairs": pairs, "pipe_counts": pipe_counts,
                            "mesh": dict(mesh.shape)}))
"""


def test_pipeline_matches_single_device_with_fused_kernels():
    res = _run_child(PIPELINE_CODE)
    assert res["mesh"] == {"stage": 2, "data": 2, "model": 2}
    assert len(res["pairs"]) == 5
    for lp, ls, gp, gs in res["pairs"]:
        assert abs(lp - ls) < 1e-3 * max(1.0, abs(ls)), (lp, ls)
        assert abs(gp - gs) < 1e-3 * max(1.0, abs(gs)), (gp, gs)
    # fused kernels active INSIDE the shard_map pipeline step: the FFN
    # megakernel (fwd + bwd), flash attention (fwd + bwd), and the fused
    # TT backward all traced at least once before the single-device step
    # ever compiled.
    c = res["pipe_counts"]
    for name in ("btt_ffn_pallas", "btt_ffn_bwd_pallas",
                 "flash_attention_pallas", "flash_attention_bwd_pallas",
                 "btt_backward_pallas"):
        assert c.get(name, 0) >= 1, (name, c)


RING_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.runtime.compress import compressed_allreduce_mean

out = {}
for n in (2, 4, 8):
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    # heavy-tailed per-shard magnitudes: re-quantizing at every hop (the
    # old bug) compounds error with ring size; quantize-once must not.
    rng = np.random.default_rng(0)
    x = np.stack([(10.0 ** (i % 3)) * rng.standard_normal(512)
                  for i in range(n)]).astype(np.float32)
    f = jax.shard_map(lambda v: compressed_allreduce_mean(v[0], "data")[None],
                  mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    got = np.asarray(jax.jit(f)(jnp.asarray(x)))
    exact = x.mean(axis=0)
    scales = np.abs(x).max(axis=1) / 127.0
    bound = scales.max() / 2.0
    err = float(np.abs(got - exact).max())
    out[str(n)] = {"err": err, "bound": float(bound)}
print("RESULT", json.dumps(out))
"""


def test_ring_allreduce_error_independent_of_ring_size():
    res = _run_child(RING_CODE)
    errs = []
    for n in ("2", "4", "8"):
        err, bound = res[n]["err"], res[n]["bound"]
        # quantize-once: every remote contribution pays exactly one int8
        # rounding, so the mean error is <= max_j scale_j / 2 for ANY n.
        assert err <= bound, (n, err, bound)
        errs.append(err)
    # and growing the ring must not grow the error past the fixed bound
    # (the re-quantizing scheme scaled roughly linearly with hops)
    assert max(errs) <= res["2"]["bound"] + res["8"]["bound"]


def test_microbatch_ragged_mask_parity():
    import jax
    import jax.numpy as jnp

    from repro.configs.atis_transformer import config_n
    from repro.launch.steps import make_train_step
    from repro.models.transformer import init_params
    from repro.optim import sgd

    cfg = config_n(2, tt_mode="tt").scaled_down(
        d_model=64, n_heads=2, d_ff=64, vocab_size=257, num_layers=2,
        max_seq_len=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = sgd(1e-2, 0.0)
    state = opt.init(params)

    k = jax.random.PRNGKey(7)
    toks = jax.random.randint(k, (4, 16), 0, cfg.vocab_size)
    # RAGGED: microbatch 0 keeps almost all tokens, microbatch 1 almost
    # none — the old unweighted mean-of-means weighted both equally.
    mask = jnp.concatenate([
        (jax.random.uniform(jax.random.fold_in(k, 1), (2, 16)) > 0.05),
        (jax.random.uniform(jax.random.fold_in(k, 2), (2, 16)) > 0.9),
    ]).astype(jnp.float32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1),
             "mask": mask}

    one = jax.jit(make_train_step(cfg, opt, microbatches=1))
    two = jax.jit(make_train_step(cfg, opt, microbatches=2))
    p1, s1, m1 = one(jax.tree.map(jnp.copy, params),
                     jax.tree.map(jnp.copy, state), batch)
    p2, s2, m2 = two(jax.tree.map(jnp.copy, params),
                     jax.tree.map(jnp.copy, state), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert jnp.allclose(a, b, atol=1e-5), (a - b)


def test_grad_norm_reported_without_clipping():
    import jax
    import jax.numpy as jnp

    from repro.configs.atis_transformer import config_n
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_ddp_train_step, make_train_step
    from repro.models.transformer import init_params
    from repro.optim import sgd
    from repro.runtime import ef_init

    cfg = config_n(2, tt_mode="tt").scaled_down(
        d_model=64, n_heads=2, d_ff=64, vocab_size=257, num_layers=2,
        max_seq_len=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = sgd(1e-2, 0.0)
    state = opt.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    step = jax.jit(make_train_step(cfg, opt, clip_norm=0.0))
    _, _, m = step(jax.tree.map(jnp.copy, params),
                   jax.tree.map(jnp.copy, state), batch)
    gn = float(m["grad_norm"])
    assert gn > 0.0 and jnp.isfinite(gn), gn

    mesh = make_mesh((1,), ("data",))
    ddp = make_ddp_train_step(cfg, opt, mesh, compress=False, clip_norm=0.0)
    _, _, _, m2 = ddp(jax.tree.map(jnp.copy, params),
                      jax.tree.map(jnp.copy, state), ef_init(params), batch)
    gn2 = float(m2["grad_norm"])
    # the old ddp builder hard-coded 0.0 here
    assert gn2 > 0.0 and jnp.isfinite(gn2), gn2
    assert abs(gn - gn2) < 1e-3 * max(1.0, gn), (gn, gn2)


def test_make_host_mesh_clamps_and_validates():
    import jax

    from repro.launch.mesh import make_host_mesh

    # data=0 used to ZeroDivisionError; now clamps to a 1x1 mesh
    mesh = make_host_mesh(0, 0)
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    # stage never silently clamps: it changes the schedule semantics
    n = len(jax.devices())
    with pytest.raises(ValueError):
        make_host_mesh(1, 1, stage=n + 1)
    assert dict(make_host_mesh(1, 1, stage=1).shape) == {"data": 1,
                                                         "model": 1}


@pytest.mark.parametrize("request_axes", [(2, 1, 1), (1, 2, 1), (1, 1, 2)])
def test_make_host_mesh_raises_past_device_count(request_axes):
    import jax

    from repro.launch.mesh import make_host_mesh

    # Oversubscribing never shrinks the mesh: the run would otherwise use
    # fewer devices than asked for without saying so.
    n = len(jax.devices())
    data, model, stage = (v * n for v in request_axes)
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data, model, stage=stage)


def test_meshes_have_auto_axes():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh, make_mesh

    for mesh in (make_mesh((1, 1), ("data", "model")), make_host_mesh(1, 1)):
        assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch.compile_cache import compile_cache_dir

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == compile_cache_dir() == os.path.join(
        root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_compile_cache_off_outside_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    # An installed copy resolves its "checkout root" to a directory with no
    # pyproject.toml: no default cache there, unless the variable names one.
    monkeypatch.setattr(compile_cache, "_ROOT", tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.enable_compile_cache() is None
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_straggler_flag_rate_post_warmup():
    from repro.runtime.straggler import StragglerMonitor

    mon = StragglerMonitor(warmup=8)
    for _ in range(10):
        mon.observe(0.1)
    assert mon.observe(0.5) is True
    # 3 post-warmup samples, 1 flagged -> 1/3.  The old denominator used
    # all 11 samples (1/11), diluting the rate CheckpointCadence keys on.
    assert mon.flag_rate == pytest.approx(1 / 3)
    mon2 = StragglerMonitor(warmup=8)
    for _ in range(5):
        mon2.observe(0.1)
    assert mon2.flag_rate == 0.0  # still inside warmup: no division blowup


def test_stage_partition_and_cycles_validation():
    from repro.configs.atis_transformer import config_n
    from repro.runtime.pipeline import (
        StagePartition,
        bubble_fraction,
        cycles_per_stage,
        stage_utilization,
    )

    part = StagePartition(stages=2, dp=2, tp=2, microbatches=2)
    assert part.devices == 8 and part.ticks == 3
    assert bubble_fraction(part) == pytest.approx(1 / 3)
    assert stage_utilization(part) == pytest.approx(2 / 3)
    with pytest.raises(ValueError):
        StagePartition(stages=0)

    cfg = config_n(4, tt_mode="tt")
    assert cycles_per_stage(cfg, 2) == 2
    with pytest.raises(ValueError):
        cycles_per_stage(cfg, 3)  # 4 cycles don't split 3 ways


def test_pipeline_ledger_per_device_envelope():
    from repro.configs.atis_transformer import config_n
    from repro.core.memory_ledger import (
        budget_report,
        pipeline_ledger_rows,
        training_step_ledger,
    )
    from repro.runtime.pipeline import StagePartition

    part = StagePartition(stages=2, dp=2, tp=2, microbatches=2)
    for n_enc in (2, 4, 6):
        cfg = config_n(n_enc, tt_mode="tt")
        rows = pipeline_ledger_rows(cfg, part, "sgd", f"pipe/{n_enc}enc")
        fits = [r for r in rows if r[0].endswith("/fits")]
        assert fits and fits[0][1] == 1.0, rows
        # partition=None stays the single-device ledger (regression)
        led0 = training_step_ledger(cfg, "sgd")
        assert budget_report(led0)["fits"]
        names0 = [e.name for e in led0["FWD"].entries]
        assert "pipeline_carries" in names0  # entry present, 0 bytes
        carry0 = led0["FWD"].entry("pipeline_carries")
        assert carry0.nbytes == 0


def test_pipeline_ledger_rows_match_tile_choosers():
    """The partitioned ledger's kernel rows ARE the kernels' tile choosers
    evaluated at the pipeline's local K (b_mb x seq) — same numbers the
    dispatch predicates see inside the shard_map body."""
    import jax

    from repro.configs.atis_transformer import config_n
    from repro.core.memory_ledger import training_step_ledger
    from repro.runtime.pipeline import StagePartition

    cfg = config_n(2, tt_mode="tt")
    part = StagePartition(stages=2, dp=2, tp=2, microbatches=2)
    batch, seq = 8, 32
    b_loc = -(-batch // (part.dp * part.tp))
    b_mb = -(-b_loc // part.microbatches)
    led = training_step_ledger(cfg, "sgd", batch=batch, seq=seq,
                               partition=part)
    led_local = training_step_ledger(cfg, "sgd", batch=b_mb, seq=seq)
    for stage, name in (("FWD", "kernel_vmem"), ("BWD", "kernel_vmem"),
                        ("FWD", "attn_kernel_vmem"),
                        ("BWD", "attn_kernel_vmem")):
        a = led[stage].entry(name)
        b = led_local[stage].entry(name)
        assert a.nbytes == b.nbytes, (stage, name, a.nbytes, b.nbytes)
