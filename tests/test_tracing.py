"""The names a train step carries (``repro.tracing``): every operation of
a compiled step falls in one stage, the model's parts and the update are
named, each kernel lies in its own stage, and ``launch.train`` writes its
host spans into a trace and reports steps that compiled again."""
import glob
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(HERE, "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.scopes import attributed, has_work, op_names, top_level  # noqa: E402
from bench_tiny import tiny_cell  # noqa: E402
from repro import tracing  # noqa: E402
from repro.tracing import part_of, stage_of  # noqa: E402

REMAT = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/attn/jit(flash_attention_pallas)/flash_fwd/"
         "pallas_call")


@pytest.mark.parametrize("op_name,stage,part", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/dot_general",
     "forward", "attn"),
    ("jit(train_step)/jvp(embed)/mul", "forward", "embed"),
    ("jit(train_step)/transpose(jvp(head))/reduce_sum", "backward", "head"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "ffn/mul", "backward", "ffn"),
    (REMAT, "recompute", "attn"),
    ("jit(train_step)/update/fused_adamw/pallas_call", "update", None),
    ("jit(train_step)/attn/cos", "forward", "attn"),
    ("jit(train_step)/convert_element_type", None, None),
    ("params['embed'].cores[1]", None, None),
    ("", None, None),
    ("jit(train_step)/jvp()/while/body/closed_call/attn/jit(headless)/add",
     "forward", "attn"),
])
def test_stage_and_part_of_an_op_name(op_name, stage, part):
    assert stage_of(op_name) == stage
    assert part_of(op_name) == part


def _compiled_text(name: str) -> str:
    """The cell's step, scaled down, compiled for the CPU from shapes."""
    from bench.program import build_cfg, param_struct
    from repro.launch.steps import make_train_step
    from repro.optim import adamw, sgd

    cell = tiny_cell(name)
    cfg, t = build_cfg(cell["config"]), cell["traffic"]
    opt = (sgd(0.1, fused=True) if t["optimizer"] == "sgd"
           else adamw(1e-3, fused=True))
    params = param_struct(cfg)
    state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq"]), dt)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("mask", jnp.float32))}
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    return step.lower(params, state, batch).compile().as_text()


KERNEL_STAGES = {
    "btt_linear": {"forward", "recompute"},
    "btt_ffn_fwd": {"forward", "recompute"},
    "flash_fwd": {"forward", "recompute"},
    "btt_backward": {"backward"}, "btt_ffn_bwd": {"backward"},
    "flash_bwd": {"backward"},
    "fused_sgd": {"update"}, "fused_adamw": {"update"},
}
_KERNEL = re.compile(r"/(%s)(?:/|$)" % "|".join(KERNEL_STAGES))


@pytest.mark.parametrize("name,update_kernel", [
    ("atis6-tt.b1s32", "fused_sgd"), ("granite8b-tt.b1s4096", "fused_adamw")])
def test_every_operation_of_a_step_has_one_stage(name, update_kernel):
    text = _compiled_text(name)
    ops, names = top_level(text), attributed(text)
    working = [n for n, op in ops.items() if has_work(op[0])]
    stages = {n: stage_of(names[n]) for n in working}
    assert not [n for n, s in stages.items() if s is None]
    assert set(stages.values()) == set(tracing.STAGES)
    assert {part_of(names[n]) for n in working} >= set(tracing.PARTS)
    # On the CPU a kernel runs interpreted, as operations (fused or not)
    # named with the kernel's ``pallas_call`` name.
    seen = {}
    for op_name in op_names(text).values():
        for kernel in _KERNEL.findall(op_name):
            seen.setdefault(kernel, set()).add(stage_of(op_name))
    assert update_kernel in seen
    assert {"btt_linear", "btt_backward", "flash_fwd", "flash_bwd"} <= set(seen)
    for kernel, got in seen.items():
        assert got <= KERNEL_STAGES[kernel], (kernel, got)
    assert "recompute" in seen["flash_fwd"]


def test_attributed_follows_users_then_operands_then_the_loop():
    text = """HloModule m

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %g = f32[4] get-tuple-element(%p), index=1
  %c = f32[4] copy(%g)
  ROOT %t = (s32[], f32[4]) tuple(%i, %c)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4] parameter(0), metadata={op_name="params[\\'w\\']"}
  %x = f32[4] copy(%a), metadata={op_name="params[\\'w\\']"}
  %y = f32[4] multiply(%x, %x), metadata={op_name="jit(step)/update/mul"}
  %z = f32[4] copy(%y)
  %w = (s32[], f32[4]) while(%t0), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp()/while"}
  ROOT %r = f32[4] add(%z, %z)
}
"""
    got = attributed(text)
    assert got["x"] == "jit(step)/update/mul"      # its reader
    assert got["z"] == "jit(step)/update/mul"      # what it reads
    assert got["c"] == "jit(step)/jvp()/while"     # the loop that runs it


def _train(*extra):
    from repro.launch.train import main

    return main(["--arch", "atis-transformer", "--scale-down", "--batch", "1",
                 "--seq", "16", "--optimizer", "sgd", "--log-every", "100",
                 *extra])


def test_train_traces_its_spans(tmp_path):
    from jax.profiler import ProfileData

    out = _train("--steps", "14", "--trace-dir", str(tmp_path))
    assert out["recompiled_steps"] == []
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    counts = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train.", "data.")):
                    counts[e.name] = counts.get(e.name, 0) + 1
    # Steps 2..11 are traced: ten of each span.
    assert counts == {"train.input": 10, "train.dispatch": 10,
                      "train.sync": 10, "data.lm_batch": 10}


def test_train_marks_a_step_that_compiled_again(monkeypatch, capsys):
    from repro.data import lm_batch
    from repro.launch import train

    def longer_at_3(seed, step, batch, seq, vocab):
        return lm_batch(seed, step, batch, seq * (2 if step == 3 else 1), vocab)

    monkeypatch.setattr(train, "lm_batch", longer_at_3)
    out = _train("--steps", "5")
    assert out["recompiled_steps"] == [3]
    lines = capsys.readouterr().out.splitlines()
    assert [ln for ln in lines if "RECOMPILED" in ln][0].startswith(
        "[train] step     3")
    assert np.all(np.isfinite(out["losses"]))


@pytest.mark.parametrize("B,S,H,KV,marked", [
    (4, 32, 4, 4, True),      # whole short sequences: a head block
    (2, 32, 8, 2, True),      # GQA
    (1, 512, 4, 2, False),    # (256, 256) tiles: one head a grid step
])
def test_flash_rows_marks_head_block_launches(B, S, H, KV, marked):
    """Both flash launches of a head block run under ``FLASH_ROWS``, so a
    compiled instruction's ``op_name`` shows which shapes took it; other
    shapes' launches carry no such scope."""
    from repro.kernels import flash_mha_op

    q = jax.ShapeDtypeStruct((B, S, H, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, S, KV, 16), jnp.float32)

    def loss(q_, k_, v_):
        return flash_mha_op(q_, k_, v_, causal=True, interpret=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    scope = re.compile(r"(?:^|[/(])%s[/)]" % tracing.FLASH_ROWS)
    for kernel in ("flash_fwd", "flash_bwd"):
        names = [n for n in op_names(text).values()
                 if f"/{kernel}/" in n and n.startswith("jit(")]
        assert names, kernel
        assert {bool(scope.search(n)) for n in names} == {marked}, kernel
