"""GPipe-style pipeline parallelism over layer stages, shard_map-native.

The paper pipelines MUL1/MUL2 *within* a layer on one chip (Sec. V);
FTRANS-style multi-chip scale-out pipelines *between* layers.  This module
composes that inter-layer pipeline with the repo's fused kernels: the mesh
carries ("stage", "data", "model") axes, every device holds the FULL
replicated parameter tree (TT compression makes it MBs — replication is the
paper's technique acting as a distributed-training optimization), and each
device runs only its stage's contiguous slice of the layer stack on its
("data" × "model") row shard of each microbatch.

Schedule (GPipe fill/drain as ONE ``jax.lax.scan`` over ticks):

    T = M + S - 1 ticks; at tick t, stage s computes microbatch i = t - s
    (ticks outside [0, M) are bubble ticks — computed uniformly for SPMD,
    masked out of the loss so they contribute no gradient).  Stage 0
    substitutes the fresh embedding of microbatch i; other stages consume
    the activation handed off by ``ppermute`` from stage s-1 at t-1.

"model" here is row-wise tensor parallelism: activations shard on their
leading batch dim, TT cores stay replicated, so the fused FFN/attention/BWD
Pallas kernels launch unchanged on local shapes — inside the shard_map body
every shape is already per-device, which is exactly what the VMEM dispatch
predicates (``ffn_vmem_fits``/``attn_bwd_vmem_fits``/``bwd_vmem_fits``)
evaluate.  Gradients ``psum`` over all three axes; the loss is the global
mask-weighted mean, so one optimizer step per device keeps params
replicated bit-for-bit.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.models.layers import rms_norm
from repro.models.transformer import (
    _embed_inputs,
    block_apply,
    lm_head,
    token_nll,
)

__all__ = [
    "PIPELINE_AXES",
    "StagePartition",
    "bubble_fraction",
    "cycles_per_stage",
    "make_pipeline_mesh",
    "pipeline_loss_and_grads",
    "stage_utilization",
]

PIPELINE_AXES = ("stage", "data", "model")


@dataclasses.dataclass(frozen=True)
class StagePartition:
    """Static shape of one multi-device training partition.

    stages × dp × tp must equal the mesh's device count; ``microbatches``
    is the GPipe schedule depth M (per-device batch rows split M ways).
    """

    stages: int = 1
    dp: int = 1
    tp: int = 1
    microbatches: int = 1

    def __post_init__(self):
        for name in ("stages", "dp", "tp", "microbatches"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got "
                                 f"{getattr(self, name)}")

    @property
    def devices(self) -> int:
        return self.stages * self.dp * self.tp

    @property
    def ticks(self) -> int:
        """Schedule length M + S - 1 (fill + steady + drain)."""
        return self.microbatches + self.stages - 1

    @classmethod
    def from_mesh(cls, mesh, microbatches: int = 1) -> "StagePartition":
        shape = dict(mesh.shape)
        return cls(stages=shape.get("stage", 1), dp=shape.get("data", 1),
                   tp=shape.get("model", 1), microbatches=microbatches)


def bubble_fraction(part: StagePartition) -> float:
    """Idle fraction of the GPipe schedule: (S-1) / (M+S-1)."""
    return (part.stages - 1) / part.ticks


def stage_utilization(part: StagePartition) -> float:
    """Busy-tick fraction per stage: M / (M+S-1) (uniform across stages)."""
    return part.microbatches / part.ticks


def cycles_per_stage(cfg: ModelConfig, stages: int) -> int:
    """Contiguous layer-cycles per pipeline stage; raises on bad splits.

    The scanned stack is organized in cycles of ``len(hybrid_pattern)``
    layers; a stage boundary inside a cycle (or a tail of unrolled layers)
    would break the uniform per-stage compute the ppermute schedule needs.
    """
    pat = len(cfg.hybrid_pattern)
    n_cycles, rem = divmod(cfg.num_layers, pat)
    if rem:
        raise ValueError(
            f"pipeline stages need tail-free configs: num_layers="
            f"{cfg.num_layers} is not a multiple of the {pat}-block "
            f"hybrid pattern")
    if stages < 1 or n_cycles == 0 or n_cycles % stages:
        raise ValueError(
            f"{n_cycles} layer cycle(s) do not split into {stages} "
            f"contiguous stage(s)")
    return n_cycles // stages


def make_pipeline_mesh(part: StagePartition):
    """(stage, data, model) mesh for ``part`` over the available devices."""
    return make_mesh((part.stages, part.dp, part.tp), PIPELINE_AXES)


def pipeline_loss_and_grads(params, cfg: ModelConfig, batch: dict,
                            part: StagePartition, *, remat: bool = True):
    """One device's slice of the GPipe step.  CALL INSIDE shard_map.

    ``batch`` leaves are this device's (dp × tp) row shard, shape
    ``(B_loc, S)``; ``params`` is the full replicated tree.  Returns
    ``(loss, grads)`` where loss is the global mask-weighted mean NLL and
    grads are f32 and already psum'd over ("stage", "data", "model") —
    identical on every device, so the caller's optimizer step keeps the
    replicated params in lockstep.

    Every psum sits OUTSIDE ``value_and_grad``: the differentiated
    function returns this device's nll contribution over the global mask
    denominator (a param-independent constant), and the psum afterwards
    reassembles both the scalar loss and the full gradient — the same
    layout ``launch.steps.make_ddp_train_step`` uses.  The only collective
    autodiff sees is the ppermute handoff, whose transpose is exact (the
    reversed ring carries activation cotangents back up the pipeline —
    GPipe's backward schedule falls out of the scan transpose for free).
    """
    cps = cycles_per_stage(cfg, part.stages)
    if cfg.frontend == "patch":
        raise NotImplementedError(
            "pipeline training does not support the patch frontend")
    pat = cfg.hybrid_pattern
    M, S_ = part.microbatches, part.stages
    stage = jax.lax.axis_index("stage")
    dt = jnp.dtype(cfg.dtype)

    if batch["tokens"].shape[0] % M:
        raise ValueError(
            f"per-device batch {batch['tokens'].shape[0]} rows do not "
            f"split into {M} microbatches")

    def split(x):
        return x.reshape((M, x.shape[0] // M) + x.shape[1:])

    mb = {k: split(v) for k, v in batch.items()}
    b_mb, seq = mb["tokens"].shape[1], mb["tokens"].shape[2]

    # Global token-weight denominator: a param-independent constant.  The
    # batch shard is replicated across "stage" (only "data"/"model" split
    # rows), so the global sum crosses those two axes only.
    if "mask" in batch:
        m_local = batch["mask"].astype(jnp.float32).sum()
    else:
        m_local = jnp.asarray(float(batch["tokens"].size), jnp.float32)
    m_global = jnp.maximum(jax.lax.psum(m_local, ("data", "model")), 1.0)

    def loss_of(p):
        # This stage's contiguous cycle slice.  dynamic_slice (traced
        # start = stage * cps) transposes to a zero-padded scatter under
        # AD, so other stages' slices get exact zero gradients — the
        # cross-stage psum then reassembles the full layer gradient.
        local_layers = jax.tree.map(
            lambda leaf: jax.lax.dynamic_slice_in_dim(
                leaf, stage * cps, cps, axis=0),
            p["layers"])

        def cycle_fn(hh, layer_params):
            for i, kind in enumerate(pat):
                hh, _ = block_apply(kind, layer_params[i], hh, cfg,
                                    cache=None, mode="train", pos=0)
            return hh, None

        cyc = jax.checkpoint(cycle_fn) if remat else cycle_fn

        def tick(carry, t):
            h_in, nll_acc = carry
            i_mb = t - stage
            valid = (i_mb >= 0) & (i_mb < M)
            idx = jnp.clip(i_mb, 0, M - 1)
            tok = jax.lax.dynamic_index_in_dim(mb["tokens"], idx, 0,
                                               keepdims=False)
            # Every stage embeds uniformly (SPMD: one program, the where
            # selects); only stage 0's embedding is live, and bubble-tick
            # garbage never reaches the loss, so it backpropagates nothing.
            emb = _embed_inputs(p, cfg, tok, None, 0).astype(dt)
            x = jnp.where(stage == 0, emb, h_in)
            y, _ = jax.lax.scan(cyc, x, local_layers)

            hn = rms_norm(y, p["final_norm"], cfg.norm_eps)
            logits = lm_head(p, cfg, hn)
            lbl = jax.lax.dynamic_index_in_dim(mb["labels"], idx, 0,
                                               keepdims=False)
            nll = token_nll(logits, lbl)
            if "mask" in mb:
                mk = jax.lax.dynamic_index_in_dim(
                    mb["mask"], idx, 0, keepdims=False).astype(jnp.float32)
            else:
                mk = jnp.ones(nll.shape, jnp.float32)
            take = (valid & (stage == S_ - 1)).astype(jnp.float32)
            nll_acc = nll_acc + take * jnp.sum(nll * mk)

            if S_ > 1:
                h_out = jax.lax.ppermute(
                    y, "stage", [(s, s + 1) for s in range(S_ - 1)])
            else:
                h_out = y
            return (h_out, nll_acc), None

        h0 = jnp.zeros((b_mb, seq, cfg.d_model), dt)
        (_, nll_sum), _ = jax.lax.scan(
            tick, (h0, jnp.zeros((), jnp.float32)),
            jnp.arange(M + S_ - 1))
        # This device's contribution to the global loss (nonzero only on
        # the last stage); psum'd below, outside autodiff.
        return nll_sum / m_global

    loss, grads = jax.value_and_grad(loss_of)(params)
    loss = jax.lax.psum(loss, PIPELINE_AXES)
    grads = jax.tree.map(
        lambda g: jax.lax.psum(g.astype(jnp.float32), PIPELINE_AXES), grads)
    return loss, grads
