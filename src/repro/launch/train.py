"""Training driver: data pipeline -> sharded train loop -> checkpoints.

Runs anywhere: the same loop drives a reduced config on the host CPU (CI,
examples) and a full config on a TPU pod slice — only the mesh and config
change.  Demonstrates the full fault-tolerance story:

  * deterministic seekable data (batch = f(seed, step)) — restart-exact
  * async atomic checkpoints with keep-k + adaptive cadence + per-leaf CRC
  * straggler monitor on per-step wall time
  * resume: picks up at the newest VALID checkpoint step (corrupt steps
    are skipped and pruned), data stream realigns
  * ``--guard``: numerics sentry + skip/backoff/rollback escalation
    (runtime.guard), chaos-tested in tests/test_robustness.py

Usage (CPU example — reduced qwen3 with the paper's TT compression):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --tt \
      --steps 50 --batch 8 --seq 128 --scale-down --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    PIPELINE_BATCH_SPEC,
    make_pipeline_train_step,
    make_train_step,
)
from repro.models.transformer import init_params, num_params, param_bytes
from repro.optim import adamw, master_view, sgd, warmup_cosine
from repro.runtime import (
    CheckpointCadence,
    StragglerMonitor,
    batch_specs,
    named_sharding_tree,
    opt_state_specs,
    param_specs,
)
from repro.tracing import span


class CompileCounter:
    """Counts JAX's tracing, lowering and compile events (``n``) from the
    moment it is made until ``close``."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event.startswith("/jax/core/compile/"):
            self.n += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def build(args):
    cfg = get_config(args.arch)
    if args.scale_down:
        cfg = cfg.scaled_down()
    if args.tt:
        cfg = cfg.with_tt(mode="tt", rank=args.tt_rank,
                          embed_rank=args.tt_rank)
    if args.kernel_flow:
        cfg = cfg.with_tt(flow="kernel")
    if args.fused_attn is not None:
        cfg = cfg.with_fused_attn(args.fused_attn)
    if args.fused_ffn is not None:
        cfg = cfg.with_fused_ffn(args.fused_ffn)
    if args.fp32:
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
    if args.param_dtype or args.act_dtype or args.grad_dtype:
        cfg = cfg.with_precision(
            **{k: v for k, v in (("param_dtype", args.param_dtype),
                                 ("act_dtype", args.act_dtype),
                                 ("grad_dtype", args.grad_dtype)) if v})
    return cfg


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--tt", action="store_true")
    ap.add_argument("--tt-rank", type=int, default=16)
    ap.add_argument("--scale-down", action="store_true")
    ap.add_argument("--fp32", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", choices=("sgd", "adamw"), default="adamw")
    ap.add_argument("--fused", action="store_true",
                    help="run the PU stage as the Pallas fused-update "
                         "kernel (interpret mode off-TPU)")
    ap.add_argument("--kernel-flow", action="store_true",
                    help="run TT linears through the fused Pallas kernels "
                         "(flow='kernel'; interpret mode off-TPU)")
    ap.add_argument("--fused-bwd", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="with --kernel-flow: run the BWD stage as the "
                         "single fused Pallas kernel (--no-fused-bwd "
                         "forces the operand-swap + XLA-GEMM path; "
                         "unset keeps the config's fused_bwd)")
    ap.add_argument("--fused-attn", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="run training attention as the fused flash "
                         "forward + single-kernel flash backward (only "
                         "(O, m, l) saved per layer; --no-fused-attn "
                         "forces the pure-JAX blockwise path; unset keeps "
                         "the config's fused_attn)")
    ap.add_argument("--fused-ffn", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="with --kernel-flow: run eligible TT FFN blocks "
                         "as the fused megakernel (both TT linears + "
                         "activation in one Pallas kernel per direction; "
                         "hidden state never leaves VMEM; --no-fused-ffn "
                         "forces the two-call path; unset keeps the "
                         "config's fused_ffn)")
    ap.add_argument("--sketched-opt", action="store_true",
                    help="with --optimizer adamw: hold the Adam moments as "
                         "count-min/count-sketch hash sketches refreshed "
                         "inside the fused PU kernel — dense m/v never "
                         "exist in HBM (falls back to dense fused AdamW "
                         "when the sketch fails sketch_pu_fits)")
    ap.add_argument("--sketch-width", type=int, default=None,
                    help="sketch buckets per row (power of two; default "
                         "default_sketch_width: ~n_params/(8*depth))")
    ap.add_argument("--sketch-depth", type=int, default=None,
                    help="sketch hash rows (default 3)")
    ap.add_argument("--param-dtype", default=None,
                    choices=("float32", "bfloat16", "int8", "fp8_e4m3"),
                    help="at-rest storage for TT half-factors AND the "
                         "fused-update master parameters (core.quant): "
                         "scaled formats dequantize inside the kernels and "
                         "re-round stochastically at the update write; "
                         "fp8 is emulated (tiles upcast to f32 in VMEM "
                         "before the dot)")
    ap.add_argument("--act-dtype", default=None,
                    choices=("float32", "bfloat16", "int8", "fp8_e4m3"),
                    help="at-rest storage for the saved backward residuals "
                         "(TT layer inputs; flash (q, k, v, o)); unset "
                         "follows the model compute dtype")
    ap.add_argument("--grad-dtype", default=None,
                    choices=("float32", "bfloat16", "fp8_e5m2"),
                    help="gradient at-rest storage between BWD and PU "
                         "(fp8_e5m2 is self-describing — no scale; int8 "
                         "is rejected)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--guard", action="store_true",
                    help="arm the training guard (runtime.guard): one "
                         "fused all-finite + grad-norm probe inside the "
                         "jitted step, EWMA loss/grad-norm spike "
                         "detection, and the skip-step -> lr-backoff -> "
                         "rollback escalation ladder; quant-saturation "
                         "sentinel auto-escalates the grad tier "
                         "fp8_e5m2->bf16 (single-device loop only)")
    ap.add_argument("--rollback-after", type=int, default=4,
                    help="with --guard: consecutive bad steps before "
                         "rolling back to the last-good snapshot / newest "
                         "valid checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="0 = adaptive cadence")
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="GPipe pipeline stages over the layer stack "
                         "(shard_map on a 'stage' mesh axis; params stay "
                         "replicated, activations hand off via ppermute). "
                         ">1 switches to make_pipeline_train_step")
    ap.add_argument("--tp", type=int, default=1,
                    help="with --pipeline-stages: row-wise tensor-parallel "
                         "shards on the 'model' axis (activation rows "
                         "split; TT cores replicated so fused kernels "
                         "stay fused)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace-dir", default=None,
                    help="profile steps start+2 .. start+11 with "
                         "jax.profiler into this directory (open it in "
                         "XProf: host spans train.* and data.*, device "
                         "scopes embed/attn/ffn/head/update)")
    args = ap.parse_args(argv)

    cfg = build(args)
    pipelined = args.pipeline_stages > 1 or args.tp > 1
    if args.guard and pipelined:
        ap.error("--guard supports the single-device loop only (the "
                 "pipeline/TP shard_map bodies own their collectives)")
    if pipelined:
        mesh = make_host_mesh(args.data_axis, args.tp,
                              stage=args.pipeline_stages)
    else:
        mesh = make_host_mesh(args.data_axis, args.model_axis)
    vocab = cfg.vocab_size

    lr = warmup_cosine(args.lr, max(args.steps // 20, 1), args.steps)
    opt = (sgd(lr, fused=args.fused) if args.optimizer == "sgd"
           else adamw(lr, fused=args.fused, sketched=args.sketched_opt,
                      sketch_width=args.sketch_width,
                      sketch_depth=args.sketch_depth,
                      param_format=cfg.tt.precision.param_dtype))

    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = opt.init(params)
    # Quantized-master states own the only parameter copy; align step 1's
    # forward with the storage grid (identity for unquantized states).
    params = master_view(opt_state, params)
    guard = None
    if args.guard:
        from repro.runtime.guard import GuardPolicy, TrainGuard
        guard = TrainGuard(GuardPolicy(rollback_after=args.rollback_after))
        # The lr_scale leaf rides in the optimizer state (checkpointed,
        # sharded replicated) so backoff/recovery never retraces the step.
        opt_state = guard.attach(opt_state)
    print(f"[train] arch={cfg.name} tt={cfg.tt.mode} params={num_params(params):,} "
          f"({param_bytes(params)/1e6:.1f} MB) mesh={dict(mesh.shape)}")

    sample = lm_batch(args.seed, 0, args.batch, args.seq, vocab)
    if pipelined:
        # shard_map owns the partitioning: params/opt state replicated,
        # batch rows split over ("data", "model").  The batch is placed
        # with that spec; params and state are left for the step to shard.
        psh = ssh = None
        bsh = named_sharding_tree(
            mesh, jax.tree.map(lambda _: PIPELINE_BATCH_SPEC, sample))
        step_fn = make_pipeline_train_step(
            cfg, opt, mesh, microbatches=args.microbatches,
            fused_bwd=args.fused_bwd)
    else:
        train_step = make_train_step(cfg, opt,
                                     microbatches=args.microbatches,
                                     fused_bwd=args.fused_bwd,
                                     guard=args.guard)
        pspec = param_specs(cfg, params, mesh)
        sspec = opt_state_specs(cfg, opt_state, pspec, mesh)
        bspec = batch_specs(sample, mesh)
        psh = named_sharding_tree(mesh, pspec)
        ssh = named_sharding_tree(mesh, sspec)
        bsh = named_sharding_tree(mesh, bspec)
        params = jax.tree.map(jax.device_put, params, psh)
        opt_state = jax.tree.map(jax.device_put, opt_state, ssh)

        if args.guard:
            # ctrl scalars replicate (no in_sharding constraint needed).
            step_fn = jax.jit(train_step,
                              in_shardings=(psh, ssh, bsh, None),
                              out_shardings=(psh, ssh, None),
                              donate_argnums=(0, 1))
        else:
            step_fn = jax.jit(train_step, in_shardings=(psh, ssh, bsh),
                              out_shardings=(psh, ssh, None),
                              donate_argnums=(0, 1))

    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)

        def template():
            p = init_params(jax.random.PRNGKey(args.seed), cfg)
            s = opt.init(p)
            return (p, guard.attach(s) if guard is not None else s)

        tmpl = jax.eval_shape(template)
        if guard is not None:
            guard.manager, guard.template = mgr, tmpl
        # Walks past corrupt/truncated steps (CRC-verified) instead of
        # crashing on a bad latest checkpoint; repairs the manifest.
        got = mgr.restore_latest_valid(tmpl)
        if got is not None:
            (params_h, opt_h), start = got
            if psh is None:
                params, opt_state = params_h, opt_h
            else:
                params = jax.tree.map(jax.device_put, params_h, psh)
                opt_state = jax.tree.map(jax.device_put, opt_h, ssh)
            print(f"[train] resumed from step {start}")

    monitor = StragglerMonitor()
    compiles = CompileCounter()
    cadence = CheckpointCadence(base_interval=max(args.steps // 4, 1),
                                min_interval=max(args.steps // 10, 1))
    trace_from = start + 2 if args.trace_dir else None
    tracing = False
    losses, grad_norms, recompiled = [], [], []
    next_ckpt = None
    for step in range(start, args.steps):
        if step == trace_from:
            jax.profiler.start_trace(args.trace_dir)
            tracing = True
        compiles.n = 0
        t0 = time.perf_counter()
        with span("train.input"):
            batch = {k: jnp.asarray(v) for k, v in
                     lm_batch(args.seed, step, args.batch, args.seq,
                              vocab).items()}
            if bsh is not None:
                batch = jax.tree.map(jax.device_put, batch, bsh)
        with span("train.dispatch"):
            if guard is not None:
                params, opt_state, metrics = step_fn(
                    params, opt_state, batch, guard.controls())
            else:
                params, opt_state, metrics = step_fn(params, opt_state, batch)
        with span("train.sync"):
            host = jax.device_get(metrics)  # the step's one device->host sync
        loss = float(host["loss"])
        dt = time.perf_counter() - t0
        flagged = monitor.observe(dt)
        action = "ok"
        if guard is not None:
            with span("train.guard"):
                params, opt_state, action = guard.observe(
                    step, metrics, params, opt_state)
        losses.append(loss)
        grad_norms.append(float(host["grad_norm"]))
        recompiled_now = compiles.n > 0 and step > start
        if recompiled_now:
            recompiled.append(step)
        if (step % args.log_every == 0 or step == args.steps - 1
                or recompiled_now):
            tag = "" if action == "ok" else f"  GUARD:{action.upper()}"
            if recompiled_now:
                tag += "  RECOMPILED"
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"{dt*1e3:7.1f} ms{'  STRAGGLER' if flagged else ''}{tag}")
        if mgr is not None:
            interval = args.ckpt_every or cadence.interval(monitor)
            if next_ckpt is None:
                next_ckpt = step + interval
            if step + 1 >= next_ckpt or step == args.steps - 1:
                with span("train.checkpoint"):
                    mgr.save_async(step + 1, (params, opt_state))
                next_ckpt = step + 1 + interval
        if tracing and step == trace_from + 9:
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    compiles.close()
    if mgr is not None:
        mgr.wait()
    out = {"final_loss": losses[-1] if losses else None,
           "first_loss": losses[0] if losses else None,
           "losses": losses,
           "grad_norms": grad_norms,
           "straggler_flags": monitor.total_flags,
           # Steps after the first that traced, lowered or compiled.
           "recompiled_steps": recompiled,
           # The config, the jitted step, the final state and the last
           # batch as placed, so a caller can check where they live and
           # inspect the program that ran.
           "cfg": cfg,
           "step_fn": step_fn,
           "params": params,
           "opt_state": opt_state,
           "batch": batch if losses else None}
    if guard is not None:
        out["guard"] = guard.report()
    return out


if __name__ == "__main__":
    enable_compile_cache()
    out = main()
    print(f"[train] done: first={out['first_loss']:.4f} "
          f"final={out['final_loss']:.4f}")
