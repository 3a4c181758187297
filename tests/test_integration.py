"""End-to-end integration: training driver (+ checkpoint resume), serving
driver, a real (subprocess) dry-run cell, and the int8 ring all-reduce on a
multi-device mesh.  Subprocesses are used wherever a different device count
is required — jax locks the platform device count at first use."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ENV = {**os.environ, "PYTHONPATH": SRC}


def test_train_driver_loss_decreases_and_resumes(tmp_path):
    from repro.launch.train import main
    ckpt = str(tmp_path / "ckpt")
    args = ["--arch", "qwen3-8b", "--tt", "--scale-down", "--steps", "16",
            "--batch", "4", "--seq", "64", "--lr", "1e-2",
            "--ckpt-dir", ckpt, "--ckpt-every", "8", "--log-every", "8"]
    out1 = main(args)
    assert out1["final_loss"] < out1["first_loss"]
    # resume: latest checkpoint is step 16 -> no steps left; extend to 24
    out2 = main(args[:5] + ["24"] + args[6:])
    assert out2["final_loss"] is not None
    from repro.checkpoint import latest_step
    assert latest_step(ckpt) == 24


def test_serve_driver_generates(tmp_path):
    from repro.launch.serve import main
    out = main(["--arch", "recurrentgemma-2b", "--scale-down", "--batch", "2",
                "--prompt-len", "32", "--gen", "8"])
    assert out["tokens"].shape == (2, 8)
    assert np.isfinite(out["tokens"]).all()


def test_serve_driver_attention_arch():
    from repro.launch.serve import main
    out = main(["--arch", "musicgen-medium", "--scale-down", "--tt",
                "--batch", "2", "--prompt-len", "16", "--gen", "4"])
    assert out["tokens"].shape == (2, 4)


@pytest.mark.parametrize("cell", [("mamba2-130m", "long_500k"),
                                  ("recurrentgemma-2b", "decode_32k")])
def test_dryrun_cell_subprocess(cell, tmp_path):
    """One real production-mesh (256-device) dry-run cell, end to end."""
    arch, shape = cell
    out_dir = str(tmp_path / "dryrun")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--out", out_dir],
        env=ENV, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    files = os.listdir(out_dir)
    assert len(files) == 1
    rec = json.load(open(os.path.join(out_dir, files[0])))
    assert rec["status"] == "ok"
    assert rec["devices"] == 256
    assert rec["cost_analysis"]["flops"] > 0
    assert rec["memory_analysis"]["temp_size_in_bytes"] > 0


def test_compressed_allreduce_subprocess():
    """int8 ring all-reduce == pmean within quantization error (8 devices)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.runtime import compressed_allreduce_mean
mesh = jax.make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
f = jax.shard_map(lambda v: compressed_allreduce_mean(v, "data"), mesh=mesh,
              in_specs=P("data", None), out_specs=P("data", None),
              check_vma=False)
y = f(x)
ref = jnp.broadcast_to(x.mean(0, keepdims=True), x.shape)
rel = float(jnp.abs(y - ref).max() / jnp.abs(ref).max())
assert rel < 0.02, rel
print("OK", rel)
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_elastic_reshard_subprocess():
    """Checkpoint on mesh A (2x4), restore+reshard on mesh B (4x2)."""
    code = """
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import init_params
from repro.checkpoint import save, restore
from repro.runtime import param_specs, named_sharding_tree
from repro.runtime.elastic import replan_for_mesh

cfg = get_config("qwen3-8b").scaled_down(d_model=256, d_ff=512, vocab_size=1024)
mesh_a = jax.make_mesh((2, 4), ("data", "model"))
params = init_params(jax.random.PRNGKey(0), cfg)
specs_a = param_specs(cfg, params, mesh_a)
params_a = jax.tree.map(jax.device_put, params, named_sharding_tree(mesh_a, specs_a))

with tempfile.TemporaryDirectory() as d:
    save(d, 3, params_a)
    tmpl = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    host, step = restore(d, tmpl)

mesh_b = jax.make_mesh((4, 2), ("data", "model"))
params_b, _ = replan_for_mesh(cfg, host, None, mesh_b)
for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK elastic", step)
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK elastic" in r.stdout


def test_microbatch_accumulation_parity():
    """make_train_step(microbatches=4) on one batch == microbatches=1:
    same loss/grad-norm metrics and the same updated parameters (the
    accumulation scan averages per-microbatch grads; with a uniform mask
    the full-batch gradient is the same average, up to f32 reordering)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import lm_batch
    from repro.launch.steps import make_train_step
    from repro.models.transformer import init_params
    from repro.optim import sgd

    cfg = (get_config("qwen3-8b").scaled_down()
           .with_tt(mode="tt", rank=8, embed_rank=8))
    opt = sgd(1e-2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = opt.init(params)
    batch = {k: jnp.asarray(v)
             for k, v in lm_batch(0, 0, 8, 64, cfg.vocab_size).items()}

    step1 = jax.jit(make_train_step(cfg, opt, microbatches=1))
    step4 = jax.jit(make_train_step(cfg, opt, microbatches=4))
    p1, s1, m1 = step1(params, state, batch)
    p4, s4, m4 = step4(params, state, batch)

    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m1["grad_norm"]),
                               float(m4["grad_norm"]), rtol=1e-4)
    assert int(s1["step"]) == int(s4["step"]) == 1
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def _teacher_forced_engine_check(cfg, *, prompt_len=6, gen=5, page_size=4,
                                 rtol=5e-4, atol=5e-5):
    """Prefill->decode through the paged engine, teacher-forced with the
    ground-truth next tokens, must reproduce the full-sequence training
    forward's logits position by position (tolerance: different tile
    accumulation orders; argmax exact)."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    from repro.models.transformer import forward
    from repro.runtime import PagedDecodeEngine

    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, cfg.vocab_size,
                         size=(1, prompt_len + gen)).astype(np.int32)
    full_logits, _ = forward(params, cfg, jnp.asarray(tokens),
                             mode="train", remat=False)
    ref = np.asarray(full_logits[0], np.float32)

    eng = PagedDecodeEngine(cfg, params, page_size=page_size,
                            max_concurrency=2,
                            max_len=prompt_len + gen + 1,
                            fused_decode=False)
    slot = 1    # off-zero slot: layout must not assume slot 0
    got = [np.asarray(eng.prefill(slot, tokens[0, :prompt_len]))]
    toks = np.zeros((2,), np.int32)
    poss = np.zeros((2,), np.int32)
    for t in range(gen):
        toks[slot] = tokens[0, prompt_len + t]
        poss[slot] = prompt_len + t
        logits = eng.decode_step(toks, poss)
        got.append(np.asarray(logits[slot], np.float32))
    for i, g in enumerate(got):
        pos = prompt_len - 1 + i
        np.testing.assert_allclose(g, ref[pos], rtol=rtol, atol=atol)
        assert (int(g[: cfg.vocab_size].argmax())
                == int(ref[pos, : cfg.vocab_size].argmax())), pos
    eng.release(slot)


@pytest.mark.parametrize("family", ["global", "local", "tt-kernel"])
def test_engine_teacher_forced_matches_training_forward(family):
    """Paged decode engine == training forward, per KV-cache family:
    global GQA attention, windowed attn_local (ring eviction), and the
    TT kernel-flow projection path."""
    import dataclasses
    from repro.configs import get_config

    cfg = get_config("llama3-8b").scaled_down()
    cfg = dataclasses.replace(cfg, dtype="float32")
    if family == "local":
        cfg = dataclasses.replace(cfg,
                                  hybrid_pattern=("attn", "attn_local"),
                                  window=6)
    elif family == "tt-kernel":
        cfg = cfg.with_tt(mode="tt", rank=8, embed_rank=8, flow="kernel")
    _teacher_forced_engine_check(cfg)


def test_serve_driver_paged_continuous_batching():
    """Paged serve path end to end on CPU: oversubscribed queue (3
    requests, 2 slots) drains with every request finished."""
    from repro.launch.serve import main
    out = main(["--arch", "llama3-8b", "--scale-down", "--tt",
                "--kernel-flow", "--batch", "3", "--prompt-len", "12",
                "--gen", "4", "--max-concurrency", "2",
                "--page-size", "4"])
    assert out["mode"] == "paged"
    assert out["tokens"].shape == (3, 4)
    assert np.isfinite(out["tokens"]).all()
    assert out["report"]["finished"] == 3
    assert out["report"]["evicted"] == 0


def test_atis_task_learns():
    """Short tensor-compressed ATIS run: joint loss drops substantially."""
    import jax
    import jax.numpy as jnp
    from repro.configs.atis_transformer import config_n
    from repro.data import AtisGrammar, atis_batch
    from repro.models import init_params
    from repro.models.classifier import atis_heads_init, atis_loss
    from repro.optim import sgd

    cfg = config_n(2).scaled_down(d_model=128, n_heads=4, d_ff=128,
                                  vocab_size=1000, num_layers=2)
    g = AtisGrammar(seed=1)
    params = {"backbone": init_params(jax.random.PRNGKey(0), cfg),
              "heads": atis_heads_init(jax.random.PRNGKey(1), cfg, 26, 120)}
    opt = sgd(0.05)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: atis_loss(p, cfg, batch))(params)
        params, state = opt.update(grads, params, state, state["step"])
        return params, state, loss

    losses = []
    for i in range(60):
        batch = {k: jnp.asarray(v)
                 for k, v in atis_batch(g, "train", i, 32).items()}
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < 0.7 * losses[0], (losses[0], losses[-1])
