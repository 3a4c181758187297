"""Step-function builders: train_step / prefill / decode, + dry-run inputs.

These close over (cfg, optimizer) and expose pure functions ready for
``jax.jit`` with explicit in/out shardings (derived by runtime.sharding).
The same builders serve the CPU examples (tiny configs, host mesh) and the
512-chip dry-run (full configs, production mesh) — there is no separate
"distributed" code path.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.transformer import cache_struct, forward, loss_fn
from repro.optim.optimizers import Optimizer, clip_by_global_norm
from repro.tracing import UPDATE

__all__ = [
    "make_train_step", "make_ddp_train_step", "make_pipeline_train_step",
    "make_prefill", "make_decode_step",
    "make_inputs", "abstract_train_state", "prepare_decode_cache",
    "PIPELINE_BATCH_SPEC",
]

# The pipeline step's batch rows split over DP x row-TP.
PIPELINE_BATCH_SPEC = PartitionSpec(("data", "model"))


# ---------------------------------------------------------------------------
# Training.
# ---------------------------------------------------------------------------


def _global_grad_norm(grads) -> jax.Array:
    """f32 global L2 norm — the reported metric when clipping is off.

    Shared by every step builder so ``grad_norm`` means the same thing
    with and without ``clip_norm`` (ddp used to report a hard 0.0)."""
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(grads)))


def _grads_at_rest(grads, cfg: ModelConfig):
    """BWD→PU boundary storage: round-trip every gradient leaf through
    ``cfg.tt.precision.grad_dtype`` (``core.quant.cast_format``) — what the
    gradient buffer holds in HBM between the backward and the update.
    fp8_e5m2's wide exponent makes it self-describing (no scale); int8 is
    rejected up front (its dynamic range collapses under one scale)."""
    gfmt = cfg.tt.precision.grad_dtype
    if gfmt == "float32":
        return grads
    if gfmt == "int8":
        raise ValueError("grad_dtype='int8' is unsupported: gradient "
                         "dynamic range collapses under a per-tensor "
                         "scale; use 'bfloat16' or 'fp8_e5m2'")
    from repro.core import quant

    return jax.tree.map(lambda g: quant.cast_format(g, gfmt), grads)


def _update(cfg: ModelConfig, opt: Optimizer, grads, params, opt_state,
            clip_norm: float):
    """Every step's tail after the gradients, under the ``update`` scope:
    grad-tier cast, clip (or the bare norm) and the optimizer update.
    Returns ``(params, opt_state, grad_norm)``."""
    with jax.named_scope(UPDATE):
        grads = _grads_at_rest(grads, cfg)
        if clip_norm:
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
        else:
            gnorm = _global_grad_norm(grads)
        params, opt_state = opt.update(grads, params, opt_state,
                                       opt_state["step"])
    return params, opt_state, gnorm


def make_train_step(cfg: ModelConfig, opt: Optimizer, *, microbatches: int = 1,
                    clip_norm: float = 1.0, remat: bool = True,
                    batch_constraint=None, fused_bwd: bool | None = None,
                    fused_attn: bool | None = None,
                    fused_ffn: bool | None = None,
                    guard: bool = False):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches > 1`` accumulates gradients over leading batch splits in a
    scan; XLA overlaps each microbatch's DP all-reduce with the next
    microbatch's backward (the grads are produced inside the scan body).
    Per-microbatch losses AND gradients are weighted by each microbatch's
    mask token count (token count when no mask) — ``loss_fn`` normalizes
    per microbatch by its own mask sum, so an unweighted mean would drift
    from the single-batch loss whenever masks are ragged across splits.

    ``batch_constraint`` (optional): applied to the reshaped
    ``(microbatches, B/mb, ...)`` batch — the reshape has no sharding
    lineage for its new leading axis, so without an explicit constraint
    GSPMD may drop the DP sharding of the per-microbatch batch (observed:
    16x activation memory on the 400B MoE cell).

    The PU stage is whatever ``opt.update`` lowers to: construct the
    optimizer with ``fused=True`` (optim.optimizers) to run it as the
    Pallas fused-update kernel, or ``adamw(sketched=True)`` to hold the
    Adam moments as hash sketches refreshed inside that kernel (dense m/v
    never exist in HBM; the init-time ``sketch_pu_fits`` fallback means the
    state layout, not this builder, decides the path).  Callers should jit
    the returned step with
    ``donate_argnums=(0, 1)`` (as launch.train does) so XLA can reuse the
    donated param/state memory across the step (the kernel's own aliasing
    is at the packed-buffer level — see kernels.fused_update).

    ``fused_bwd`` (optional) overrides ``cfg.tt.fused_bwd`` for this step:
    with ``flow="kernel"``, True runs the BWD stage as the single fused
    Pallas kernel (``kernels.btt_backward``), False the operand-swap +
    XLA-GEMM reference path.  ``None`` keeps the config's setting.

    ``fused_attn`` (optional) likewise overrides ``cfg.fused_attn``: True
    runs training attention as the fused flash forward + single-kernel
    flash backward (only ``(O, m, l)`` saved per layer — no S×S
    probabilities), False the pure-JAX blockwise path under autodiff.

    ``fused_ffn`` (optional) likewise overrides ``cfg.fused_ffn``: with
    ``flow="kernel"``, True runs every eligible TT FFN block (incl.
    per-expert MoE FFNs) as the fused megakernel — both TT linears +
    activation in one Pallas kernel per direction, hidden state
    VMEM-resident, backward recomputing it from the layer input; False
    the two-call (three when gated) path.

    ``guard=True`` changes the signature to ``(params, opt_state, batch,
    ctrl) -> (params, opt_state, metrics)`` and routes the tail of the
    step through ``runtime.guard.apply_guarded_update``: one fused
    norm/all-finite reduction, the grad-tier escalation select, and the
    skip-step mask that keeps params AND the full optimizer state (dense,
    sketched, quant-master) untouched on a non-finite step.  ``ctrl``
    comes from ``TrainGuard.controls()`` (or ``guard_controls()``);
    metrics gain ``nonfinite``/``sat_frac``/``applied``.  The pipeline
    and DDP builders do not take a guard (their shard_map bodies own the
    collectives); ``launch.train`` rejects the combination.
    """
    if fused_bwd is not None:
        cfg = cfg.with_tt(fused_bwd=fused_bwd)
    if fused_attn is not None:
        cfg = cfg.with_fused_attn(fused_attn)
    if fused_ffn is not None:
        cfg = cfg.with_fused_ffn(fused_ffn)

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn)(params, cfg, batch, remat=remat)

    def loss_and_grads(params, batch):
        if microbatches == 1:
            loss, grads = grads_of(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)
            if batch_constraint is not None:
                mb = batch_constraint(mb)
            # Derive the f32 accumulator FROM params (p * 0) so it inherits
            # the parameter sharding — a bare jnp.zeros has no sharding
            # lineage and GSPMD may replicate 400B-class f32 accumulators.
            acc0 = jax.tree.map(
                lambda p: (p * 0).astype(jnp.float32), params)

            def body(acc, one):
                l, g = grads_of(params, one)
                m = one.get("mask")
                w = (m.astype(jnp.float32).sum() if m is not None
                     else jnp.asarray(float(one["labels"].size), jnp.float32))
                # g is d(nll_i/w_i)/dp — scale back to the nll_i gradient
                # so the accumulated sum divides by the GLOBAL token count.
                acc = jax.tree.map(
                    lambda a, gg: a + w * gg.astype(jnp.float32), acc, g)
                return acc, (l, w)

            grads, (losses, ws) = jax.lax.scan(body, acc0, mb)
            wsum = jnp.maximum(ws.sum(), 1.0)
            grads = jax.tree.map(lambda g: g / wsum, grads)
            loss = (losses * ws).sum() / wsum
        return loss, grads

    if guard:
        from repro.runtime.guard import apply_guarded_update

        def guarded_step(params, opt_state, batch, ctrl):
            loss, grads = loss_and_grads(params, batch)
            # The guarded tail owns the grad-tier cast (it needs both the
            # configured tier and the bf16 escalation in the graph) and
            # the clip (it reuses the finite-probe reduction as the norm).
            return apply_guarded_update(
                opt, loss, grads, params, opt_state, ctrl,
                grad_fmt=cfg.tt.precision.grad_dtype, clip_norm=clip_norm)

        return guarded_step

    def train_step(params, opt_state, batch):
        loss, grads = loss_and_grads(params, batch)
        params, opt_state, gnorm = _update(cfg, opt, grads, params, opt_state,
                                           clip_norm)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_ddp_train_step(cfg: ModelConfig, opt: Optimizer, mesh, *,
                        compress: bool = True, clip_norm: float = 1.0):
    """Pure data-parallel step via shard_map with an int8 ring all-reduce.

    The natural pairing for the paper's technique: TT params are MBs and
    replicate for free, so DP is the whole story — and the gradient
    all-reduce (already 30-52x smaller from compression of the *model*)
    travels int8 with error feedback (runtime/compress.py) for another 4x.

    State: (params, opt_state, ef_residuals).  Returns a jitted callable
    ``(params, opt_state, ef, batch) -> (params, opt_state, ef, metrics)``.

    A ``fused=True`` optimizer composes with this path: params are
    replicated per-shard inside shard_map, so the fused PU kernel runs on
    each device's full (tiny, TT-compressed) parameter set — args 0/1 are
    donated below so XLA can reuse their memory across the step.
    """
    from jax.sharding import PartitionSpec as P

    from repro.runtime.compress import compressed_allreduce_mean, ef_compress_tree

    def step(params, opt_state, ef, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if compress:
            grads, ef = ef_compress_tree(grads, ef)
            grads = jax.tree.map(
                lambda g: compressed_allreduce_mean(g, "data"), grads)
        else:
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "data"), grads)
        loss = jax.lax.pmean(loss, "data")
        params, opt_state, gnorm = _update(cfg, opt, grads, params, opt_state,
                                           clip_norm)
        return params, opt_state, ef, {"loss": loss, "grad_norm": gnorm}

    rep = P()
    batch_spec = P("data")
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False,  # ring ppermute breaks the replication checker
    )
    return jax.jit(mapped, donate_argnums=(0, 1, 2))


def make_pipeline_train_step(cfg: ModelConfig, opt: Optimizer, mesh, *,
                             microbatches: int = 1, clip_norm: float = 1.0,
                             remat: bool = True,
                             fused_bwd: bool | None = None,
                             fused_attn: bool | None = None,
                             fused_ffn: bool | None = None):
    """Pipeline × row-TP × DP training via shard_map, fused kernels fused.

    ``mesh`` must carry the ("stage", "data", "model") axes
    (``launch.mesh.make_host_mesh(stage=...)`` or
    ``runtime.pipeline.make_pipeline_mesh``).  Params and optimizer state
    replicate on every device — TT compression makes the whole tree MBs,
    so replication is free and there is no weight-sharding story to
    maintain; what scales out is COMPUTE: "stage" pipelines contiguous
    layer cycles GPipe-style over ``microbatches`` (ppermute handoff,
    fill/drain in one lax.scan — see runtime.pipeline), while "data" and
    "model" both shard activation rows ("model" is row-wise TP: each
    device launches the fused FFN/attention/BWD Pallas kernels on its own
    row shard, so the VMEM dispatch predicates see local shapes and
    fusion survives the mesh).  Gradients psum over all three axes and
    every device runs the identical optimizer update, keeping params
    replicated bit-for-bit.

    The global batch must divide by dp × tp × microbatches.  Loss is the
    global mask-weighted mean, so metrics match ``make_train_step`` on the
    same batch to f32 accumulation-order tolerance (asserted per step in
    tests/test_pipeline.py).  ``fused_*`` override the config knobs as in
    ``make_train_step``.  Returns a jitted
    ``(params, opt_state, batch) -> (params, opt_state, metrics)`` with
    args 0/1 donated.
    """
    from jax.sharding import PartitionSpec as P

    from repro.runtime.pipeline import (
        StagePartition,
        cycles_per_stage,
        pipeline_loss_and_grads,
    )

    if fused_bwd is not None:
        cfg = cfg.with_tt(fused_bwd=fused_bwd)
    if fused_attn is not None:
        cfg = cfg.with_fused_attn(fused_attn)
    if fused_ffn is not None:
        cfg = cfg.with_fused_ffn(fused_ffn)

    part = StagePartition.from_mesh(mesh, microbatches)
    cycles_per_stage(cfg, part.stages)  # validate the layer split up front

    def step(params, opt_state, batch):
        loss, grads = pipeline_loss_and_grads(params, cfg, batch, part,
                                              remat=remat)
        params, opt_state, gnorm = _update(cfg, opt, grads, params, opt_state,
                                           clip_norm)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    rep = P()
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, PIPELINE_BATCH_SPEC),
        out_specs=(rep, rep, rep),
        check_vma=False,  # stage ppermute breaks the replication checker
    )
    return jax.jit(mapped, donate_argnums=(0, 1))


def abstract_train_state(cfg: ModelConfig, opt: Optimizer):
    """(params, opt_state) as ShapeDtypeStructs — dry-run stand-ins."""
    from repro.models.transformer import init_params
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(opt.init, params)
    return params, opt_state


# ---------------------------------------------------------------------------
# Serving.
# ---------------------------------------------------------------------------


def make_prefill(cfg: ModelConfig):
    """(params, batch) -> (last_logits (B, 1, Vp), cache)."""

    def prefill(params, batch):
        logits, cache = forward(params, cfg, batch["tokens"],
                                patches=batch.get("patches"),
                                mode="prefill", remat=False)
        return logits[:, -1:, :], cache

    return prefill


def make_decode_step(cfg: ModelConfig):
    """(params, cache, tokens (B,1), pos ()) -> (logits (B,1,Vp), cache)."""

    def decode_step(params, cache, tokens, pos):
        logits, new_cache = forward(params, cfg, tokens, cache=cache,
                                    mode="decode", pos=pos, remat=False)
        return logits, new_cache

    return decode_step


def prepare_decode_cache(cfg: ModelConfig, prefill_cache: Any, prefill_len: int,
                         max_len: int, *, kv_repeat: int = 1) -> Any:
    """Convert a prefill cache into the decode layout.

    Attention KV: repeat heads to the TP degree, place into a zeroed
    ``max_len`` buffer (ring placement for windowed layers).  SSM / RG-LRU
    states come out of prefill already decode-ready and pass through.
    """
    def fix(leaf):
        if not isinstance(leaf, dict):
            return leaf
        return leaf

    def fix_kv(k: jax.Array, window: int | None) -> jax.Array:
        B, S, KV, dh = k.shape
        if kv_repeat > 1:
            k = jnp.repeat(k, kv_repeat, axis=2)
            KV *= kv_repeat
        if window is None:
            buf = jnp.zeros((B, max_len, KV, dh), k.dtype)
            return jax.lax.dynamic_update_slice(buf, k, (0, 0, 0, 0))
        w = min(window, max_len)
        buf = jnp.zeros((B, w, KV, dh), k.dtype)
        take = min(S, w)
        tail = k[:, S - take:, :, :]
        slots = (jnp.arange(S - take, S) % w)
        return buf.at[:, slots].set(tail)

    def walk(tree, kinds):
        out = []
        for blk, kind in zip(tree, kinds):
            if blk is None:
                out.append(None)
            elif "k" in blk and "v" in blk:
                window = cfg.window if kind == "attn_local" else None
                out.append({"k": fix_kv(blk["k"], window),
                            "v": fix_kv(blk["v"], window)})
            else:
                out.append(fix(blk))
        return tuple(out)

    pat = cfg.hybrid_pattern
    n_cycles = cfg.num_layers // len(pat)
    tail_kinds = pat[: cfg.num_layers - n_cycles * len(pat)]
    new = {"layers": None, "tail": ()}
    if prefill_cache["layers"] is not None:
        # stacked leaves have a leading cycle dim — vmap the fix over it
        def fix_stacked(blk, kind):
            if blk is None:
                return None
            if isinstance(blk, dict) and "k" in blk:
                window = cfg.window if kind == "attn_local" else None
                return {"k": jax.vmap(lambda a: fix_kv(a, window))(blk["k"]),
                        "v": jax.vmap(lambda a: fix_kv(a, window))(blk["v"])}
            return blk
        new["layers"] = tuple(
            fix_stacked(blk, kind)
            for blk, kind in zip(prefill_cache["layers"], pat))
    new["tail"] = walk(prefill_cache["tail"], tail_kinds)
    return new


# ---------------------------------------------------------------------------
# Dry-run inputs (ShapeDtypeStruct stand-ins; no allocation).
# ---------------------------------------------------------------------------


def make_inputs(cfg: ModelConfig, shape: ShapeConfig, *,
                kv_repeat: int = 1) -> dict:
    """Abstract inputs for one (arch x shape) cell.

    train:   {batch: {tokens, labels, mask [, patches]}}
    prefill: {batch: {tokens [, patches]}}
    decode:  {cache, tokens (B, 1), pos ()}   (serve_step: one new token
             against a seq_len cache — never a train_step)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
            "mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
        }
        if cfg.frontend == "patch":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_len, cfg.d_model), dt)
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.frontend == "patch":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_len, cfg.d_model), dt)
        return {"batch": batch}
    if shape.kind == "decode":
        cache = cache_struct(cfg, B, S, kv_repeat=kv_repeat)
        return {
            "cache": cache,
            "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
        }
    raise ValueError(shape.kind)
