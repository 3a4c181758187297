"""Per-training-stage on-chip residency ledger (paper Sec. III-A / Table IV).

The paper's headline hardware claim is an *on-chip-memory-only framework for
each stage in training*: forward (FWD), backward (BWD), and parameter update
(PU) all run against a <6 MB BRAM + 22.5 MB URAM budget on the ZCU102.  This
module makes that claim *checkable in software*: for a model config it
builds, per stage, the list of buffers that must be live at once, maps each
onto the paper's two pools, and flags the peak against the budget envelope.

Pools (the TPU/VMEM analogue keeps the paper's split):

* ``bram`` — persistent, parameter-like residency: TT/TTM cores, biases,
  and optimizer moments.  The paper streams these from BRAM every cycle
  (Eqs. (22)-(25) size the blocks; ``cost_model.bram_blocks`` models them).
* ``uram`` — transient, stage-scoped residency: activations/residuals,
  gradients, and contraction intermediates.  These are the K-sized buffers
  the paper's URAM holds between stages.

Byte counts come from two places, both already validated elsewhere:

* exact pytree accounting (``jax.eval_shape`` over ``init_params`` /
  ``opt.init``) for parameters, moments, and gradients;
* the paper's closed forms in ``cost_model`` (Eq. (21) ``mem_btt``) for the
  contraction intermediates, evaluated over the actual ``TTSpec``s found in
  the parameter tree — so ledger totals agree with the cost model by
  construction (asserted in tests/test_fused_update.py).

Activation residuals are first-order: the fused BTT VJP saves only each
layer's *inputs* (see ``core.tt_linear._btt_fused_fwd``), so the ledger
counts one ``(K, N)`` input per TT linear plus the attention residuals —
the autodiff-saved S×S probabilities on the blockwise path, or only
``(O, m, l)`` per layer with ``cfg.fused_attn`` (the fused flash backward
recomputes probability tiles in VMEM; ``attn_residual_bytes`` is the single
source for both numbers, and the ledger gates on the same
``attn_bwd_vmem_fits`` the op dispatches on).  Shared inputs (Q/K/V
projections read the same ``x``) are counted once per projection — a
deliberate over-count, i.e. the "fits" verdict is conservative.

FFN blocks follow the same contract: with ``cfg.fused_ffn`` on the kernel
flow and the block passing ``models.layers.ffn_fused_eligible`` — the
EXACT predicate function ``mlp_apply``/``moe._expert_ffn_apply`` dispatch
on (all-TT, bias-free, no model-parallel mesh, VMEM fit at the launch's
own K) — the ledger drops the
down projection's ``(K, d_ff)`` saved input and the activation pre-images
(``ffn_hidden`` row) and instead reports the megakernel's tile-derived
working set (``ffn_kernel_vmem`` row) — FFN residuals are O(K·d_model),
never O(K·d_ff), exactly what the op actually saves.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .cost_model import mem_btt
from .tt import TTSpec
from .tt_linear import TTLinearParams
from .ttm_embedding import TTMEmbeddingParams

__all__ = [
    "BRAM_BUDGET_BYTES",
    "URAM_BUDGET_BYTES",
    "LedgerEntry",
    "StageLedger",
    "training_step_ledger",
    "pipeline_ledger_rows",
    "decode_step_ledger",
    "budget_report",
    "format_report",
    "ledger_rows",
    "decode_ledger_rows",
]

BRAM_BUDGET_BYTES = 6 * 2**20            # paper: <6 MB BRAM
URAM_BUDGET_BYTES = int(22.5 * 2**20)    # paper: 22.5 MB URAM
STAGES = ("FWD", "BWD", "PU")


@dataclasses.dataclass(frozen=True)
class LedgerEntry:
    name: str
    nbytes: int
    pool: str  # "bram" | "uram"
    note: str = ""


@dataclasses.dataclass(frozen=True)
class StageLedger:
    stage: str
    entries: tuple[LedgerEntry, ...]

    @property
    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self.entries)

    def pool_bytes(self, pool: str) -> int:
        return sum(e.nbytes for e in self.entries if e.pool == pool)

    def entry(self, name: str) -> LedgerEntry:
        for e in self.entries:
            if e.name == name:
                return e
        raise KeyError(name)


# ---------------------------------------------------------------------------
# Pytree accounting helpers.
# ---------------------------------------------------------------------------


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _tree_count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def _collect_modules(params) -> tuple[list[TTLinearParams], list[TTMEmbeddingParams]]:
    """All TT linear / TTM embedding modules in a parameter pytree (the
    dataclass nodes survive ``jax.eval_shape``; specs are static aux)."""
    tts: list[TTLinearParams] = []
    ttms: list[TTMEmbeddingParams] = []

    def visit(node):
        if isinstance(node, TTLinearParams):
            tts.append(node)
        elif isinstance(node, TTMEmbeddingParams):
            ttms.append(node)
        return node

    jax.tree.map(visit, params,
                 is_leaf=lambda n: isinstance(n, (TTLinearParams,
                                                  TTMEmbeddingParams)))
    return tts, ttms


def _stacked_multiplier(module) -> int:
    """Layer-stacked modules (vmapped cycles) carry a leading stack dim on
    every core; the spec describes ONE layer.  Infer the multiplier."""
    core = module.cores[0]
    spec_rank0 = module.spec.core_shapes()[0]
    return int(core.shape[0]) if len(core.shape) == len(spec_rank0) + 1 else 1


def _btt_kernel_vmem_bytes(spec: TTSpec, itemsize: int, K: int) -> int:
    """VMEM working set of one ``btt_linear_pallas`` grid step — the
    kernel's own tile chooser (with the step's actual K), so ledger and
    kernel cannot drift."""
    from repro.kernels.btt_linear import choose_tiles

    return choose_tiles(spec.out_dim, spec.mid_rank, itemsize, K=K)[4]


def _btt_bwd_kernel_vmem_bytes(spec: TTSpec, itemsize: int, K: int,
                               fused: bool) -> int:
    """VMEM working set of the BWD-stage launch for one layer — the fused
    ``btt_backward_pallas`` kernel's when ``cfg.tt.fused_bwd`` and it fits
    the budget (the path ``kernels.ops`` takes), else the operand-swap
    forward launch's.  Derived by the same chooser the kernel launches
    with, so ledger and tiles cannot drift (the FWD stage makes the
    identical promise)."""
    from repro.kernels.btt_backward import bwd_stage_vmem_bytes

    return bwd_stage_vmem_bytes(spec.out_dim, spec.in_dim, spec.mid_rank,
                                itemsize, K=K, fused=fused)


def _pu_kernel_vmem_bytes(n_params: int, n_bufs: int) -> int:
    """VMEM working set of one fused-update grid step: ``n_bufs`` blocks of
    (block_rows, lanes) f32 (params + grads + moments, outputs aliased)."""
    from repro.kernels.fused_update import pu_block_shape

    br, _, lanes = pu_block_shape(n_params)
    return n_bufs * br * lanes * 4


def _attn_kernel_vmem_bytes(cfg, batch: int, seq: int, itemsize: int,
                            stage: str) -> int:
    """VMEM working set of the attention-stage flash launch over ``batch``
    sequences — derived from the BACKWARD kernel's own tile chooser
    (``choose_attn_tiles``, head block included), so ledger and launched
    blocks cannot drift; 0 when ``fused_attn`` is off or the shape falls
    back to the pure-JAX blockwise path."""
    from repro.kernels.flash_backward import attn_stage_vmem_bytes

    return attn_stage_vmem_bytes(seq, cfg.d_head, itemsize,
                                 rows=batch * cfg.n_heads,
                                 group=cfg.n_heads // cfg.n_kv_heads,
                                 stage=stage, fused=cfg.fused_attn)


# ---------------------------------------------------------------------------
# FFN blocks (structural walk: up/down[/gate] triples in mlp and MoE dicts).
# ---------------------------------------------------------------------------


def _collect_ffn_blocks(params) -> list[dict]:
    """Every FFN block in a parameter pytree: dicts holding an
    ``up``/``down`` (and optionally ``gate``) projection triple — plain
    MLPs, per-expert MoE stacks, and MoE shared experts alike."""
    blocks: list[dict] = []

    def walk(node):
        if isinstance(node, dict):
            if "up" in node and "down" in node:
                blocks.append(node)
                if isinstance(node.get("shared"), dict):
                    walk(node["shared"])
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return blocks


def _ffn_block_mult(m: TTLinearParams) -> int:
    """Stack multiplier of one FFN projection: the product of all leading
    dims beyond the spec's own core rank (cycle-stacked layers contribute
    one axis, vmapped MoE experts another)."""
    core = m.cores[0]
    base = len(m.spec.core_shapes()[0])
    return int(np.prod(core.shape[: len(core.shape) - base])) or 1


def _ffn_block_dims(blk: dict):
    """(M, N, F, R1, R2, Rg, gated, mult) for an all-TT block, else None."""
    up, down = blk["up"], blk["down"]
    gate = blk.get("gate")
    mods = (up, down) if gate is None else (up, down, gate)
    if not all(isinstance(m, TTLinearParams) for m in mods):
        return None
    return (down.spec.out_dim, up.spec.in_dim, up.spec.out_dim,
            up.spec.mid_rank, down.spec.mid_rank,
            gate.spec.mid_rank if gate is not None else 0,
            gate is not None, _ffn_block_mult(down))


# ---------------------------------------------------------------------------
# The ledger.
# ---------------------------------------------------------------------------


def training_step_ledger(cfg, optimizer: str = "sgd", *, momentum: float = 0.0,
                         batch: int = 1, seq: int = 32,
                         sketched: bool = False,
                         sketch_width: int | None = None,
                         sketch_depth: int | None = None,
                         partition=None) -> dict[str, StageLedger]:
    """Per-stage (FWD/BWD/PU) peak-residency ledgers for one training step.

    ``optimizer`` sizes the moment buffers: "sgd" (none, or one with
    ``momentum``) or "adamw" (two).  ``sketched=True`` (adamw only) charges
    the count-min/count-sketch moment state instead of the dense buffers —
    by CONSTRUCTION of the same ``optim.adamw(sketched=True)`` init the
    training step runs (the state layout from ``jax.eval_shape`` IS the
    dispatch decision, including the ``sketch_pu_fits`` fallback), so the
    ledger cannot drift from the op.  ``batch=1, seq=32`` is the paper's
    regime (Sec. VI).  Everything is derived from ``jax.eval_shape`` — no
    device memory is allocated.

    ``partition`` (optional ``runtime.pipeline.StagePartition``) reports
    PER-DEVICE residency for the pipeline × row-TP × DP training step:
    params/grads/moments stay whole (the tree replicates — it is MBs under
    TT compression), kernel-launch rows shrink to one microbatch's row
    shard (``ceil(batch / (dp·tp·microbatches)) · seq`` — the exact K the
    per-device dispatch predicates and tile choosers see inside shard_map),
    stacked-layer residuals scale by this stage's cycle fraction, and the
    GPipe handoff carries get their own uram row.  ``None`` is exactly the
    single-device ledger.
    """
    from repro.core import quant as _q
    from repro.models.transformer import init_params
    from repro.optim import adamw as _adamw, sgd as _sgd

    if partition is not None:
        from repro.runtime.pipeline import cycles_per_stage

        n_cycles = cfg.num_layers // max(len(cfg.hybrid_pattern), 1)
        stage_frac = cycles_per_stage(cfg, partition.stages) / n_cycles
        b_loc = -(-batch // (partition.dp * partition.tp))
        b_mb = -(-b_loc // partition.microbatches)
    else:
        stage_frac = 1.0
        b_loc = b_mb = batch
    # Two row counts: K is what one kernel LAUNCH sees (a single
    # microbatch's row shard — the dispatch predicates' argument); K_res is
    # what stays RESIDENT (at the GPipe peak every in-flight microbatch's
    # residuals are live, so residency uses the whole local batch).
    K = b_mb * seq
    K_res = b_loc * seq
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    prec = cfg.tt.precision
    param_fmt = prec.param_dtype
    act_fmt = prec.resolved_act(cfg.dtype)
    grad_fmt = prec.grad_dtype
    if optimizer == "adamw":
        opt = _adamw(1e-3, sketched=sketched, sketch_width=sketch_width,
                     sketch_depth=sketch_depth, param_format=param_fmt)
    else:
        opt = _sgd(1e-3, momentum)
    opt_state = jax.eval_shape(opt.init, params)

    # Two itemsizes per tier: compute (kernel-VMEM rows, contraction
    # transients — f32 accumulator chains regardless of storage) and
    # AT-REST storage (what HBM holds between stages: core.quant formats).
    act_itemsize = jnp.dtype(cfg.dtype).itemsize
    act_store = _q.itemsize(act_fmt)
    params_bytes = _tree_bytes(params)
    n_params = _tree_count(params)
    # Gradient at-rest tier between BWD and PU (steps._grads_at_rest).
    grads_bytes = n_params * _q.itemsize(grad_fmt)
    moments_bytes = _tree_bytes(opt_state) - 4  # minus the int32 step scalar
    # Quantized-master state: (pq, ps) ARE the parameters — split them out
    # of the moment accounting and charge them as the PU params row.
    if isinstance(opt_state, dict) and "pq" in opt_state:
        pu_params_bytes = (int(np.prod(opt_state["pq"].shape))
                           * jnp.dtype(opt_state["pq"].dtype).itemsize
                           + int(np.prod(opt_state["ps"].shape)) * 4)
        moments_bytes -= pu_params_bytes
        pu_params_note = (f"quantized master ({param_fmt} packed + "
                          "per-block f32 scales; SR re-round in-kernel)")
    else:
        pu_params_bytes = params_bytes
        pu_params_note = "updated in place"

    tts, ttms = _collect_modules(params)
    specs = [m.spec for m in tts]
    # FWD/BWD weight tier: half-factors at the param storage format (one
    # f32 scale per half-factor — each IS a single VMEM tile); identity
    # when param_dtype is the compute dtype.
    if param_fmt not in ("float32", cfg.dtype):
        fwd_params_bytes = _q.quantized_bytes(
            n_params, param_fmt, n_scales=2 * max(len(tts), 1))
        fwd_params_note = (f"TT cores + norms at rest in {param_fmt} "
                           "(kernels dequantize tiles in VMEM)")
    else:
        fwd_params_bytes = params_bytes
        fwd_params_note = "TT/TTM cores + biases + norms (eval_shape-exact)"

    # Contraction intermediates (paper Eq. (21)): layers run sequentially,
    # so the live set is the *largest* layer's, not the sum.
    tt_inter_peak = max(
        (mem_btt(s, K) * act_itemsize for s in specs), default=0)

    # FFN blocks: with cfg.fused_ffn on the kernel flow and the block
    # passing THE dispatch predicate itself — models.layers.
    # ffn_fused_eligible, the exact function mlp_apply/_expert_ffn_apply
    # gate on (all-TT, bias-free, no model-parallel mesh, megakernel
    # working set inside the VMEM budget) — the hidden state is recomputed
    # in VMEM, so the down projection's (K, d_ff) input and the activation
    # pre-images are never saved.  Otherwise the two-call path saves both.
    from repro.kernels.btt_ffn import (
        ffn_residual_bytes,
        ffn_stage_vmem_bytes,
    )
    from repro.models.layers import ffn_fused_eligible

    ffn_hidden_bytes = 0
    ffn_fwd_vmem = 0
    ffn_bwd_vmem = 0
    ffn_fused_any = False
    excluded_down_ids: set[int] = set()
    for blk in _collect_ffn_blocks(params):
        dims = _ffn_block_dims(blk)
        if dims is None:
            continue
        M_, N_, F_, R1, R2, Rg, gated, mult = dims
        # The row count the model actually dispatches with: MoE expert
        # blocks (the dict also carries the router) run per expert on the
        # capacity-dispatched (G*cap) tokens, not on batch*seq — the
        # predicate and tile chooser must see the launch's own K or the
        # ledger drifts from moe._expert_ffn_apply.
        if "router" in blk and cfg.moe is not None:
            cap = int(math.ceil(seq * cfg.moe.top_k / cfg.moe.num_experts
                                * cfg.moe.capacity_factor))
            k_blk = b_mb * cap
        else:
            k_blk = K
        # Same gate the model applies: fused_ffn refines the kernel flow
        # only, and the block must pass the dispatch predicate.
        fused_eff = (cfg.fused_ffn and cfg.tt.flow == "kernel"
                     and ffn_fused_eligible(blk["up"], blk["down"],
                                            blk.get("gate"), K=k_blk))
        if fused_eff:
            ffn_fused_any = True
            excluded_down_ids.add(id(blk["down"]))
            ffn_fwd_vmem = max(ffn_fwd_vmem, ffn_stage_vmem_bytes(
                M_, N_, F_, R1, R2, Rg, act_itemsize, K=k_blk,
                stage="FWD"))
            ffn_bwd_vmem = max(ffn_bwd_vmem, ffn_stage_vmem_bytes(
                M_, N_, F_, R1, R2, Rg, act_itemsize, K=k_blk,
                stage="BWD"))
        else:
            # Pre-activation residuals only: the down projection's saved
            # (K, F) input is charged by the per-TT-linear loop below (at
            # the ledger's K convention), so subtract its term from the
            # closed form to avoid counting it twice.  Residency counts the
            # whole local batch (K_res) and only this stage's share of
            # stacked layers.
            eff_mult = mult if mult == 1 else max(round(mult * stage_frac), 1)
            ffn_hidden_bytes += eff_mult * (
                ffn_residual_bytes(K_res, F_, act_store, gated=gated,
                                   fused=False)
                - K_res * F_ * act_store)

    # Residuals the fused VJP saves for BWD: one (K, N) input per TT-linear
    # application (stacked modules apply once per stacked layer).  Down
    # projections of megakernel-dispatched FFN blocks save NOTHING — their
    # input is the VMEM-recomputed hidden state.
    n_tt_apps = 0
    resid_bytes = 0
    for m in tts:
        if id(m) in excluded_down_ids:
            continue
        mult = _stacked_multiplier(m)
        # Stacked (layer-cycle) modules: this stage holds only its cycle
        # slice; top-level modules (head/intent) apply once per device.
        eff_mult = mult if mult == 1 else max(round(mult * stage_frac), 1)
        n_tt_apps += eff_mult
        resid_bytes += eff_mult * K_res * m.spec.in_dim * act_store
    # Attention residuals, per layer: the autodiff-saved (B, h, S, S)
    # probabilities on the blockwise path, or only (O, m, l) with
    # fused_attn — gated on the SAME attn_bwd_vmem_fits the op dispatches
    # on, so the ledger reports the path actually taken.
    from repro.kernels.flash_backward import (
        attn_bwd_vmem_fits,
        attn_residual_bytes,
    )

    n_layers = max(round(cfg.num_layers * stage_frac), 1)
    attn_fused_eff = cfg.fused_attn and attn_bwd_vmem_fits(
        seq, cfg.d_head, act_itemsize)
    attn_resid = n_layers * attn_residual_bytes(
        b_loc, cfg.n_heads, seq, cfg.d_head, act_store,
        fused=attn_fused_eff)
    attn_note = ("(O, m, l) per layer — flash bwd recomputes probability "
                 "tiles in VMEM; no S×S residual"
                 if attn_fused_eff else
                 "autodiff-saved S×S attention probabilities per layer")
    # Embedding output + positional sum, the first saved activation
    # (one per TTM/dense embedding module).  Under a pipeline partition
    # every stage embeds (uniform SPMD program), so the row stays whole.
    embed_act = max(len(ttms), 1) * K_res * cfg.d_model * act_store
    resid_total = resid_bytes + embed_act
    # GPipe handoff carries: the tick scan saves one (b_mb, seq, d_model)
    # boundary activation per tick for its backward.
    if partition is not None and partition.stages > 1:
        carry_bytes = (partition.ticks * b_mb * seq * cfg.d_model
                       * act_store)
        carry_note = (f"ppermute handoffs: {partition.ticks} tick(s) x "
                      f"({b_mb}, {seq}, {cfg.d_model}) saved for BWD")
    else:
        carry_bytes = 0
        carry_note = "no pipeline stages (single-stage schedule)"

    fwd_kernel_vmem = max(
        (_btt_kernel_vmem_bytes(s, act_itemsize, K) for s in specs),
        default=0)
    bwd_kernel_vmem = max(
        (_btt_bwd_kernel_vmem_bytes(s, act_itemsize, K, cfg.tt.fused_bwd)
         for s in specs),
        default=0)
    attn_fwd_vmem = _attn_kernel_vmem_bytes(cfg, b_mb, seq, act_itemsize,
                                            "FWD")
    attn_bwd_vmem = _attn_kernel_vmem_bytes(cfg, b_mb, seq, act_itemsize,
                                            "BWD")
    # Live VMEM blocks per fused_update grid step = the input buffer list
    # (outputs are aliased onto inputs): (p, g) / (p, mu, g) / (p, m, v, g).
    # On the sketched path the working set comes from the sketched kernel's
    # own residency helper instead (param + grad blocks + all six resident
    # (depth, width) sketch blocks) — gated on the state layout eval_shape
    # produced, i.e. the exact sketch_pu_fits verdict the op dispatches on.
    sketched_eff = isinstance(opt_state, dict) and "vs" in opt_state
    if sketched_eff:
        from repro.kernels.fused_update import sketch_pu_vmem_bytes

        s_depth, s_width = opt_state["vs"].shape
        pu_kernel_vmem = sketch_pu_vmem_bytes(
            n_params, s_width, s_depth, itemsize=act_itemsize)
        pu_vmem_note = (f"sketched_adamw_update: p+g blocks + 6 resident "
                        f"({s_depth}, {s_width}) sketch blocks")
        moments_note = (f"count-min/count-sketch moments "
                        f"({s_depth}x{s_width} x2, sketch_pu_fits-gated)")
    else:
        n_pu_bufs = {"sgd": 3 if momentum else 2, "adamw": 4}[optimizer]
        pu_kernel_vmem = _pu_kernel_vmem_bytes(n_params, n_pu_bufs)
        pu_vmem_note = f"fused_update: {n_pu_bufs} live blocks per grid step"
        moments_note = f"{optimizer} optimizer state (eval_shape-exact)"

    ffn_hidden_note = (
        "megakernel recomputes the hidden tile in VMEM — no pre-activation "
        "or hidden residual" if ffn_fused_any and ffn_hidden_bytes == 0 else
        "activation pre-images saved between the two-call FFN launches")
    fwd = StageLedger("FWD", (
        LedgerEntry("params", fwd_params_bytes, "bram", fwd_params_note),
        LedgerEntry("residuals", resid_total, "uram",
                    f"fused-VJP saved inputs ({n_tt_apps} TT apps) "
                    "+ embed"),
        LedgerEntry("attn_residuals", attn_resid, "uram", attn_note),
        LedgerEntry("ffn_hidden", ffn_hidden_bytes, "uram",
                    ffn_hidden_note),
        LedgerEntry("tt_intermediates", tt_inter_peak, "uram",
                    "paper Eq. (21) mem_btt, max over layers"),
        LedgerEntry("kernel_vmem", fwd_kernel_vmem, "uram",
                    "btt_linear_pallas working set, largest layer"),
        LedgerEntry("attn_kernel_vmem", attn_fwd_vmem, "uram",
                    "flash_attention_pallas working set (fused_attn)"
                    if attn_fused_eff else
                    "no flash launch (blockwise path)"),
        LedgerEntry("ffn_kernel_vmem", ffn_fwd_vmem, "uram",
                    "btt_ffn_pallas working set (choose_ffn_tiles-derived), "
                    "largest block" if ffn_fused_any else
                    "no megakernel launch (two-call path)"),
        LedgerEntry("pipeline_carries", carry_bytes, "uram", carry_note),
    ))
    grads_note = ("f32 accumulators" if grad_fmt == "float32" else
                  f"gradient at-rest tier in {grad_fmt} "
                  "(steps cast at the BWD->PU boundary)")
    bwd = StageLedger("BWD", (
        LedgerEntry("params", fwd_params_bytes, "bram",
                    "re-read for half-factor rebuild"),
        LedgerEntry("residuals", resid_total, "uram",
                    "consumed as BWD walks the graph"),
        LedgerEntry("attn_residuals", attn_resid, "uram", attn_note),
        LedgerEntry("ffn_hidden", ffn_hidden_bytes, "uram",
                    ffn_hidden_note),
        LedgerEntry("grads", grads_bytes, "uram", grads_note),
        LedgerEntry("tt_intermediates", tt_inter_peak, "uram",
                    "t = x @ B^T recomputed per layer (never stored)"),
        LedgerEntry("kernel_vmem", bwd_kernel_vmem, "uram",
                    ("btt_backward_pallas working set (gx/ga/gb one pass), "
                     "largest layer") if cfg.tt.fused_bwd else
                    "operand-swap btt_linear_pallas working set "
                    "(fused_bwd=False)"),
        LedgerEntry("attn_kernel_vmem", attn_bwd_vmem, "uram",
                    "flash_attention_bwd_pallas working set "
                    "(choose_attn_tiles-derived: dQ/dK/dV one pass)"
                    if attn_fused_eff else
                    "no flash launch (blockwise path)"),
        LedgerEntry("ffn_kernel_vmem", ffn_bwd_vmem, "uram",
                    "btt_ffn_bwd_pallas working set (hidden recomputed in "
                    "VMEM; gx + all half-factor grads one pass)"
                    if ffn_fused_any else
                    "no megakernel launch (two-call path)"),
        LedgerEntry("pipeline_carries", carry_bytes, "uram", carry_note),
    ))
    pu = StageLedger("PU", (
        LedgerEntry("params", pu_params_bytes, "bram", pu_params_note),
        LedgerEntry("moments", moments_bytes, "bram", moments_note),
        LedgerEntry("grads", grads_bytes, "uram", "consumed by the update"),
        LedgerEntry("kernel_vmem", pu_kernel_vmem, "uram", pu_vmem_note),
    ))
    return {"FWD": fwd, "BWD": bwd, "PU": pu}


def decode_step_ledger(cfg, *, batch: int = 1, max_len: int = 128,
                       page_size: int = 64,
                       fused: bool = True) -> StageLedger:
    """DECODE-stage peak residency for one continuous-batched serving step.

    Serving inverts the training split: weights stay the persistent (bram)
    pool exactly as in training, but the GROWING state is now the paged KV
    pool, sized by the same ``runtime.kv_cache`` layout the
    ``PagedDecodeEngine`` allocates (groups from the engine's own
    ``_layout``, page count from ``max_pages_per_request``) — ledger and
    allocator cannot drift.  Kernel-VMEM rows are gated on the SAME
    ``decode_*_vmem_fits`` predicates ``kernels.ops`` dispatches the decode
    specializations on.  Only attention-family configs page
    (``paged_supported``); others raise.
    """
    from repro.kernels.btt_ffn import decode_ffn_stage_vmem_bytes
    from repro.kernels.btt_linear import decode_linear_stage_vmem_bytes
    from repro.kernels.flash_decode import decode_attn_stage_vmem_bytes
    from repro.models.transformer import init_params
    from repro.runtime.decode_engine import _layout, paged_supported
    from repro.runtime.kv_cache import kv_pool_bytes, max_pages_per_request

    if not paged_supported(cfg):
        raise ValueError(f"decode ledger needs attention-family blocks, "
                         f"got {cfg.hybrid_pattern}")
    from repro.core import quant as _q

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    act_itemsize = jnp.dtype(cfg.dtype).itemsize
    params_bytes = _tree_bytes(params)
    param_fmt = cfg.tt.precision.param_dtype
    if param_fmt not in ("float32", cfg.dtype):
        # Serving tier: weights at rest in the param format (the decode ops
        # round-trip through it — core.quant.cast_format).
        n_w = _tree_count(params)
        n_tt = len(_collect_modules(params)[0])
        params_bytes = _q.quantized_bytes(n_w, param_fmt,
                                          n_scales=2 * max(n_tt, 1))
        params_note = f"weights at rest in {param_fmt} (decode round-trips)"
    else:
        params_note = "TT/TTM cores + biases + norms (eval_shape-exact)"
    B = batch

    # Paged KV pools, one per window group — the engine's own layout.
    n_cycles, _, _, n_pat, n_tail, windows = _layout(cfg)
    kv_bytes = 0
    for gid, window in windows.items():
        n_layers = n_cycles * n_pat.get(gid, 0) + n_tail.get(gid, 0)
        np_max = max_pages_per_request(max_len, page_size, window)
        kv_bytes += kv_pool_bytes(n_layers, 1 + B * np_max, cfg.n_kv_heads,
                                  page_size, cfg.d_head, act_itemsize)

    # Transient per-step activations: residual stream + norm temp + the
    # q/k/v/attn-out columns of the live layer (layers run sequentially).
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    act_bytes = B * (3 * cfg.d_model + (2 * H + 2 * KV) * dh) * act_itemsize
    logits_bytes = B * cfg.vocab_padded * act_itemsize

    tts, _ = _collect_modules(params)
    lin_vmem = max(
        (decode_linear_stage_vmem_bytes(m.spec.out_dim, m.spec.mid_rank,
                                        act_itemsize, B=B, fused=fused)
         for m in tts), default=0)
    G = H // KV
    attn_vmem = decode_attn_stage_vmem_bytes(G, dh, page_size, act_itemsize,
                                             fused=fused)
    ffn_vmem = 0
    ffn_hidden = 0
    for blk in _collect_ffn_blocks(params):
        dims = _ffn_block_dims(blk)
        if dims is None or not (fused and cfg.fused_ffn
                                and cfg.tt.flow == "kernel"):
            F = (dims[2] if dims is not None
                 else getattr(cfg, "d_ff", cfg.d_model * 4))
            ffn_hidden = max(ffn_hidden, B * F * act_itemsize)
            continue
        M_, N_, F_, R1, R2, Rg, _, _ = dims
        v = decode_ffn_stage_vmem_bytes(M_, N_, F_, R1, R2, Rg,
                                        act_itemsize, B=B, fused=True)
        if v:
            ffn_vmem = max(ffn_vmem, v)
        else:
            ffn_hidden = max(ffn_hidden, B * F_ * act_itemsize)

    return StageLedger("DECODE", (
        LedgerEntry("params", params_bytes, "bram", params_note),
        LedgerEntry("kv_pages", kv_bytes, "uram",
                    f"paged KV pools ({len(windows)} group(s), "
                    f"page={page_size}, {B} slot(s), max_len={max_len})"),
        LedgerEntry("activations", act_bytes, "uram",
                    "residual stream + live layer's q/k/v/o columns"),
        LedgerEntry("logits", logits_bytes, "uram",
                    "one decode step's (B, Vp) logits"),
        LedgerEntry("attn_kernel_vmem", attn_vmem, "uram",
                    "flash_decode_pallas working set "
                    "(choose_decode_attn_tiles-derived)" if attn_vmem else
                    "no flash-decode launch (paged pure-JAX ref)"),
        LedgerEntry("kernel_vmem", lin_vmem, "uram",
                    "btt_linear_decode_pallas working set, largest layer"
                    if lin_vmem else "no decode TT-linear launch"),
        LedgerEntry("ffn_kernel_vmem", ffn_vmem, "uram",
                    "btt_ffn_decode_pallas working set "
                    "(choose_decode_ffn_tiles-derived)" if ffn_vmem else
                    "no decode megakernel launch"),
        LedgerEntry("ffn_hidden", ffn_hidden, "uram",
                    "two-call FFN hidden column (no megakernel)"
                    if ffn_hidden else
                    "hidden state VMEM-resident in the megakernel"),
    ))


def decode_ledger_rows(cfg, prefix: str, *, batch: int = 1,
                       max_len: int = 128, page_size: int = 64,
                       fused: bool = True) -> list[tuple[str, float, str]]:
    """Benchmark rows for one serving config: DECODE-stage MB + fits flag
    against the paper's envelope (bram = weights, uram = KV pages +
    transients) — shared by bench_decode and launch.serve."""
    led = decode_step_ledger(cfg, batch=batch, max_len=max_len,
                             page_size=page_size, fused=fused)
    mb = 1 / 2**20
    bram = led.pool_bytes("bram")
    uram = led.pool_bytes("uram")
    fits = bram <= BRAM_BUDGET_BYTES and uram <= URAM_BUDGET_BYTES
    return [
        (f"{prefix}/DECODE_mb", led.total_bytes * mb,
         f"bram {bram * mb:.3f} MB + uram {uram * mb:.3f} MB"),
        (f"{prefix}/fits", 1.0 if fits else 0.0,
         f"peak bram {bram * mb:.2f}/6.0 MB; uram {uram * mb:.2f}/22.5 MB; "
         f"batch={batch} max_len={max_len} page={page_size}"),
    ]


def budget_report(ledgers: dict[str, StageLedger]) -> dict[str, Any]:
    """Peak per-pool residency across stages vs the paper's envelope."""
    bram_peak = max(ledgers[s].pool_bytes("bram") for s in STAGES)
    uram_peak = max(ledgers[s].pool_bytes("uram") for s in STAGES)
    return {
        "bram_peak_bytes": bram_peak,
        "uram_peak_bytes": uram_peak,
        "bram_budget_bytes": BRAM_BUDGET_BYTES,
        "uram_budget_bytes": URAM_BUDGET_BYTES,
        "fits_bram": bram_peak <= BRAM_BUDGET_BYTES,
        "fits_uram": uram_peak <= URAM_BUDGET_BYTES,
        "fits": (bram_peak <= BRAM_BUDGET_BYTES
                 and uram_peak <= URAM_BUDGET_BYTES),
        "peak_stage_bytes": {s: ledgers[s].total_bytes for s in STAGES},
    }


def ledger_rows(cfg, optimizer: str, prefix: str, *, momentum: float = 0.0,
                sketched: bool = False, batch: int = 1, seq: int = 32,
                partition=None,
                fits_note: str = "") -> list[tuple[str, float, str]]:
    """Benchmark rows for one config: per-stage MB + a fits flag.

    Shared by bench_memory and bench_pu so the emitted names/notes cannot
    diverge.  Notes are CSV-safe ("; "-separated — benchmarks.run emits
    bare 3-column ``name,value,note`` lines).  With ``partition`` the rows
    are PER-DEVICE (see ``training_step_ledger``).
    """
    led = training_step_ledger(cfg, optimizer, momentum=momentum,
                               sketched=sketched, batch=batch, seq=seq,
                               partition=partition)
    rep = budget_report(led)
    mb = 1 / 2**20
    out: list[tuple[str, float, str]] = []
    for stage in STAGES:
        out.append((
            f"{prefix}/{stage}_mb", led[stage].total_bytes * mb,
            f"bram {led[stage].pool_bytes('bram') * mb:.3f} MB + "
            f"uram {led[stage].pool_bytes('uram') * mb:.3f} MB"))
    note = (f"peak bram {rep['bram_peak_bytes'] * mb:.2f}/6.0 MB; "
            f"uram {rep['uram_peak_bytes'] * mb:.2f}/22.5 MB")
    if fits_note:
        note += f"; {fits_note}"
    out.append((f"{prefix}/fits", 1.0 if rep["fits"] else 0.0, note))
    return out


def pipeline_ledger_rows(cfg, partition, optimizer: str, prefix: str, *,
                         momentum: float = 0.0, sketched: bool = False,
                         batch: int | None = None,
                         seq: int = 32) -> list[tuple[str, float, str]]:
    """Per-device ledger rows for one pipeline × row-TP × DP partition.

    ``batch`` defaults to one row per (dp × tp × microbatch) slot — the
    smallest batch the partition can run — matching the paper's batch=1
    single-device regime scaled to the mesh.  Shared by bench_training's
    ``--devices`` mode and tests/test_pipeline.py.
    """
    if batch is None:
        batch = partition.dp * partition.tp * partition.microbatches
    return ledger_rows(
        cfg, optimizer, prefix, momentum=momentum, sketched=sketched,
        batch=batch, seq=seq, partition=partition,
        fits_note=(f"per-device: stages={partition.stages} "
                   f"dp={partition.dp} tp={partition.tp} "
                   f"mb={partition.microbatches} batch={batch} seq={seq}"))


def format_report(ledgers: dict[str, StageLedger]) -> str:
    """Human-readable ledger table (used by benchmarks and docs examples)."""
    rep = budget_report(ledgers)
    mb = 1 / 2**20
    lines = []
    for s in STAGES:
        led = ledgers[s]
        lines.append(f"{s}: {led.total_bytes * mb:.3f} MB "
                     f"(bram {led.pool_bytes('bram') * mb:.3f}, "
                     f"uram {led.pool_bytes('uram') * mb:.3f})")
        for e in led.entries:
            lines.append(f"    {e.name:<18} {e.nbytes * mb:8.3f} MB "
                         f"[{e.pool}]  {e.note}")
    lines.append(
        f"peak: bram {rep['bram_peak_bytes'] * mb:.3f}/"
        f"{rep['bram_budget_bytes'] * mb:.1f} MB "
        f"({'OK' if rep['fits_bram'] else 'OVER'}), "
        f"uram {rep['uram_peak_bytes'] * mb:.3f}/"
        f"{rep['uram_budget_bytes'] * mb:.1f} MB "
        f"({'OK' if rep['fits_uram'] else 'OVER'})")
    return "\n".join(lines)
