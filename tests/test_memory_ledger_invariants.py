"""Memory-ledger invariants over EVERY shipped config.

The ledger's contract is that its per-stage kernel rows are *derived from
the kernels' own tile choosers* — the residency it reports is the residency
the launched tiles imply, with no second bookkeeping that could drift.
These tests walk every registered arch (TT-compressed, scaled to the CPU
test regime for the non-paper archs), recompute each stage's working set
straight from ``choose_tiles`` / ``bwd_stage_vmem_bytes`` /
``pu_block_shape``, and assert byte-for-byte equality with the ledger —
plus the paper's envelope checks: kernel working sets fit the 22.5 MB URAM
pool everywhere, and the paper's own ATIS models fit the full
6 MB BRAM + 22.5 MB URAM budget at every stage.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.atis_transformer import config_n
from repro.core.memory_ledger import (
    BRAM_BUDGET_BYTES,
    URAM_BUDGET_BYTES,
    _collect_modules,
    budget_report,
    training_step_ledger,
)
from repro.kernels.btt_backward import bwd_stage_vmem_bytes
from repro.kernels.btt_linear import choose_tiles
from repro.kernels.fused_update import pu_block_shape

BATCH, SEQ = 1, 32          # the paper's training regime (Sec. VI)
K = BATCH * SEQ


def _tt_config(arch):
    cfg = get_config(arch)
    if arch != "atis-transformer":
        cfg = cfg.scaled_down().with_tt(mode="tt", rank=8, embed_rank=8)
    return cfg


def _abstract_params(cfg):
    from repro.models.transformer import init_params
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def _specs(cfg):
    tts, _ = _collect_modules(_abstract_params(cfg))
    return [m.spec for m in tts]


@pytest.mark.parametrize("arch", list_archs())
def test_kernel_rows_are_chooser_derived(arch):
    """FWD and BWD kernel_vmem == the max over TT layers of the values the
    tile choosers return for this step's K — recomputed here independently
    of the ledger's own code path."""
    cfg = _tt_config(arch)
    led = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    specs = _specs(cfg)
    assert specs, f"{arch}: TT mode produced no TT layers"

    fwd_expect = max(
        choose_tiles(s.out_dim, s.mid_rank, itemsize, K=K)[4] for s in specs)
    bwd_expect = max(
        bwd_stage_vmem_bytes(s.out_dim, s.in_dim, s.mid_rank, itemsize, K=K)
        for s in specs)
    assert led["FWD"].entry("kernel_vmem").nbytes == fwd_expect
    assert led["BWD"].entry("kernel_vmem").nbytes == bwd_expect


@pytest.mark.parametrize("arch", list_archs())
def test_kernel_working_sets_fit_uram_envelope(arch):
    """Every stage's kernel-derived VMEM working set fits the paper's
    22.5 MB URAM pool — the transient on-chip residency the kernels are
    designed around (the PU row is checked against its own chooser too)."""
    cfg = _tt_config(arch)
    led = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD", "PU"):
        kv = led[stage].entry("kernel_vmem").nbytes
        assert kv <= URAM_BUDGET_BYTES, (arch, stage, kv)

    params = _abstract_params(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    br, _, lanes = pu_block_shape(n)
    assert led["PU"].entry("kernel_vmem").nbytes == 2 * br * lanes * 4


def test_bwd_row_tracks_fused_bwd_flag():
    """With fused_bwd=False the op launches the operand-swap forward kernel
    instead of btt_backward_pallas; the ledger's BWD row must follow the
    flag (no drift in either direction)."""
    cfg = config_n(2)
    itemsize = jnp.dtype(cfg.dtype).itemsize
    specs = _specs(cfg)
    led_off = training_step_ledger(cfg.with_tt(fused_bwd=False), "sgd",
                                   batch=BATCH, seq=SEQ)
    expect_off = max(
        bwd_stage_vmem_bytes(s.out_dim, s.in_dim, s.mid_rank, itemsize,
                             K=K, fused=False) for s in specs)
    expect_swap = max(
        choose_tiles(s.in_dim, s.mid_rank, itemsize, K=K)[4] for s in specs)
    assert led_off["BWD"].entry("kernel_vmem").nbytes == expect_off
    assert expect_off == expect_swap  # the operand-swap launch's tiles
    led_on = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    assert (led_on["BWD"].entry("kernel_vmem").nbytes
            != led_off["BWD"].entry("kernel_vmem").nbytes)


@pytest.mark.parametrize("fused_attn", [False, True])
@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_paper_atis_models_fit_full_envelope(n_enc, fused_attn):
    """The paper's central claim for its own models: every training stage
    of the 2/4/6-encoder ATIS transformer fits 6 MB BRAM + 22.5 MB URAM,
    with the BWD row derived from the fused backward kernel — and with the
    attention stage on either path (fused flash kernels / blockwise)."""
    cfg = config_n(n_enc).with_fused_attn(fused_attn)
    led = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    rep = budget_report(led)
    assert rep["fits_bram"] and rep["fits_uram"] and rep["fits"]
    assert rep["bram_peak_bytes"] <= BRAM_BUDGET_BYTES
    assert rep["uram_peak_bytes"] <= URAM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# Attention rows: chooser-derived, and no S×S residual under fused_attn.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", list_archs())
def test_attn_kernel_rows_are_chooser_derived(arch):
    """With fused_attn the FWD/BWD attn_kernel_vmem rows must equal the
    flash backward kernel's own tile-chooser numbers (recomputed here
    independently, at the batch's B·H rows, which set the head block);
    without it, 0 — no Pallas launch on the blockwise path."""
    from repro.kernels.flash_backward import attn_stage_vmem_bytes

    cfg = _tt_config(arch)
    itemsize = jnp.dtype(cfg.dtype).itemsize

    led_on = training_step_ledger(cfg.with_fused_attn(True), "sgd",
                                  batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        expect = attn_stage_vmem_bytes(SEQ, cfg.d_head, itemsize,
                                       rows=BATCH * cfg.n_heads,
                                       group=cfg.n_heads // cfg.n_kv_heads,
                                       stage=stage, fused=True)
        assert led_on[stage].entry("attn_kernel_vmem").nbytes == expect
        assert expect <= URAM_BUDGET_BYTES

    led_off = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        assert led_off[stage].entry("attn_kernel_vmem").nbytes == 0


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_fused_attn_reports_no_sxs_probability_residual(n_enc):
    """Acceptance: with fused_attn=True the ledger charges only (O, m, l)
    per layer — byte-for-byte the attn_residual_bytes closed form, never
    the S×S probabilities the blockwise path saves."""
    from repro.kernels.flash_backward import attn_residual_bytes

    cfg = config_n(n_enc)
    its = jnp.dtype(cfg.dtype).itemsize
    probs = cfg.num_layers * BATCH * cfg.n_heads * SEQ * SEQ * its
    oml = cfg.num_layers * attn_residual_bytes(
        BATCH, cfg.n_heads, SEQ, cfg.d_head, its, fused=True)

    led = training_step_ledger(cfg.with_fused_attn(True), "sgd",
                               batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        got = led[stage].entry("attn_residuals").nbytes
        assert got == oml
        assert got != probs
        assert "S×S" not in led[stage].entry("attn_residuals").note \
            or "no S×S" in led[stage].entry("attn_residuals").note

    led_off = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    assert led_off["FWD"].entry("attn_residuals").nbytes == probs


# ---------------------------------------------------------------------------
# FFN rows: chooser-derived, residual shrink, gated on the dispatch predicate.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_ffn_kernel_rows_are_chooser_derived(n_enc):
    """With fused_ffn the FWD/BWD ffn_kernel_vmem rows must equal the
    megakernel's own tile-chooser numbers (recomputed here independently
    of the ledger); without it, 0 — no megakernel launch on the two-call
    path."""
    from repro.core.memory_ledger import _collect_ffn_blocks, _ffn_block_dims
    from repro.kernels.btt_ffn import ffn_stage_vmem_bytes

    cfg = config_n(n_enc).with_tt(flow="kernel")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    params = _abstract_params(cfg)
    dims = [d for d in (_ffn_block_dims(b)
                        for b in _collect_ffn_blocks(params))
            if d is not None]
    assert dims

    led_on = training_step_ledger(cfg.with_fused_ffn(True), "sgd",
                                  batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        expect = max(ffn_stage_vmem_bytes(M, N, F, R1, R2, Rg, itemsize,
                                          K=K, stage=stage)
                     for M, N, F, R1, R2, Rg, _, _ in dims)
        assert led_on[stage].entry("ffn_kernel_vmem").nbytes == expect
        assert expect <= URAM_BUDGET_BYTES

    led_off = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        assert led_off[stage].entry("ffn_kernel_vmem").nbytes == 0


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_fused_ffn_residuals_shrink_to_layer_input(n_enc):
    """Acceptance: with fused_ffn the ledger drops exactly the FFN hidden
    state — the down projection's (K, d_ff) saved input leaves the
    residuals row and the activation pre-images (ffn_hidden) go to zero,
    so FFN residuals are O(K*d_model), not O(K*d_ff)."""
    cfg = config_n(n_enc).with_tt(flow="kernel")
    its = jnp.dtype(cfg.dtype).itemsize
    led_off = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    led_on = training_step_ledger(cfg.with_fused_ffn(True), "sgd",
                                  batch=BATCH, seq=SEQ)
    hidden = cfg.num_layers * K * cfg.d_ff * its  # one (K, d_ff) per block
    for stage in ("FWD", "BWD"):
        drop = (led_off[stage].entry("residuals").nbytes
                - led_on[stage].entry("residuals").nbytes)
        assert drop == hidden
        # ungated GELU FFN: one pre-activation per block on the two-call
        # path, none with the megakernel.
        assert led_off[stage].entry("ffn_hidden").nbytes == hidden
        assert led_on[stage].entry("ffn_hidden").nbytes == 0


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_paper_atis_models_fit_envelope_with_fused_ffn(n_enc):
    """The paper's envelope claim survives the megakernel: every stage of
    the ATIS models still fits 6 MB BRAM + 22.5 MB URAM with fused_ffn on
    (alone and together with fused_attn)."""
    base = config_n(n_enc).with_tt(flow="kernel")
    for cfg in (base.with_fused_ffn(True),
                base.with_fused_ffn(True).with_fused_attn(True)):
        led = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
        rep = budget_report(led)
        assert rep["fits_bram"] and rep["fits_uram"] and rep["fits"]


def test_ffn_rows_gate_on_vmem_fits_predicate():
    """A config whose FFN busts the megakernel budget must ledger exactly
    like fused_ffn=False even when the flag is on — the SAME predicate the
    op dispatches on (no drift between ledger and dispatch)."""
    from repro.core.memory_ledger import _collect_ffn_blocks, _ffn_block_dims
    from repro.kernels.btt_ffn import ffn_vmem_fits

    cfg = (get_config("qwen3-8b")
           .with_tt(mode="tt", rank=64, embed_rank=64,
                    flow="kernel"))  # full-size d_ff
    itemsize = jnp.dtype(cfg.dtype).itemsize
    params = _abstract_params(cfg)
    dims = [d for d in (_ffn_block_dims(b)
                        for b in _collect_ffn_blocks(params))
            if d is not None]
    assert dims
    assert all(not ffn_vmem_fits(M, N, F, R1, R2, Rg, itemsize, K=K)
               for M, N, F, R1, R2, Rg, _, _ in dims)
    led_on = training_step_ledger(cfg.with_fused_ffn(True), "sgd",
                                  batch=BATCH, seq=SEQ)
    led_off = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        for row in ("residuals", "ffn_hidden", "ffn_kernel_vmem"):
            assert (led_on[stage].entry(row).nbytes
                    == led_off[stage].entry(row).nbytes)
        assert led_on[stage].entry("ffn_kernel_vmem").nbytes == 0


def test_ffn_rows_require_kernel_flow():
    """fused_ffn refines the kernel flow only (like tt.fused_bwd): on a
    pure-JAX flow the model never dispatches the megakernel, and the
    ledger must agree — no ffn_kernel_vmem, no residual shrink."""
    cfg = config_n(2).with_fused_ffn(True)  # default flow: btt_fused
    assert cfg.tt.flow != "kernel"
    led = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    led_ref = training_step_ledger(config_n(2), "sgd", batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        assert led[stage].entry("ffn_kernel_vmem").nbytes == 0
        assert (led[stage].entry("residuals").nbytes
                == led_ref[stage].entry("residuals").nbytes)
        assert (led[stage].entry("ffn_hidden").nbytes
                == led_ref[stage].entry("ffn_hidden").nbytes)


def test_ffn_rows_use_moe_expert_dispatch_k():
    """MoE expert blocks dispatch the megakernel per expert on the
    capacity-dispatched (G*cap) rows, not on batch*seq — the ledger's
    ffn_kernel_vmem rows must be the chooser's numbers at THAT K."""
    import math

    from repro.core.memory_ledger import _collect_ffn_blocks, _ffn_block_dims
    from repro.kernels.btt_ffn import ffn_stage_vmem_bytes

    cfg = (get_config("qwen2-moe-a2.7b").scaled_down()
           .with_tt(mode="tt", rank=8, embed_rank=8, flow="kernel")
           .with_fused_ffn(True))
    itemsize = jnp.dtype(cfg.dtype).itemsize
    m = cfg.moe
    cap = int(math.ceil(SEQ * m.top_k / m.num_experts * m.capacity_factor))
    params = _abstract_params(cfg)
    led = training_step_ledger(cfg, "sgd", batch=BATCH, seq=SEQ)
    for stage in ("FWD", "BWD"):
        expect = 0
        for blk in _collect_ffn_blocks(params):
            dims = _ffn_block_dims(blk)
            if dims is None:
                continue
            M_, N_, F_, R1, R2, Rg, _, _ = dims
            k_blk = BATCH * cap if "router" in blk else K
            expect = max(expect, ffn_stage_vmem_bytes(
                M_, N_, F_, R1, R2, Rg, itemsize, K=k_blk, stage=stage))
        assert led[stage].entry("ffn_kernel_vmem").nbytes == expect


# ---------------------------------------------------------------------------
# Sketched-AdamW PU rows: kernel-helper-derived, envelope, moment shrink.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_sketched_pu_rows_are_kernel_helper_derived(n_enc):
    """With sketched AdamW, the PU rows must equal the sketched kernel's
    OWN size helpers — moments == sketch_state_bytes at the state's actual
    (depth, width), kernel_vmem == sketch_pu_vmem_bytes — recomputed here
    independently of the ledger."""
    from repro.kernels.fused_update import (
        SKETCH_DEPTH_DEFAULT,
        default_sketch_width,
        sketch_pu_vmem_bytes,
        sketch_state_bytes,
    )

    cfg = config_n(n_enc)
    its = jnp.dtype(cfg.dtype).itemsize
    params = _abstract_params(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    depth = SKETCH_DEPTH_DEFAULT
    width = default_sketch_width(n, depth)

    led = training_step_ledger(cfg, "adamw", batch=BATCH, seq=SEQ,
                               sketched=True)
    pu = led["PU"]
    assert pu.entry("moments").nbytes == sketch_state_bytes(depth, width)
    assert pu.entry("kernel_vmem").nbytes == sketch_pu_vmem_bytes(
        n, width, depth, itemsize=its)
    assert "sketch" in pu.entry("moments").note


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_sketched_pu_moments_at_least_4x_smaller(n_enc):
    """Acceptance: on every shipped ATIS config, the sketched PU moment
    row is >= 4x smaller than dense AdamW's moment footprint, and the
    full step stays inside the 6 + 22.5 MB envelope with strictly smaller
    persistent (bram) PU residency."""
    cfg = config_n(n_enc)
    led_d = training_step_ledger(cfg, "adamw", batch=BATCH, seq=SEQ)
    led_s = training_step_ledger(cfg, "adamw", batch=BATCH, seq=SEQ,
                                 sketched=True)
    dense = led_d["PU"].entry("moments").nbytes
    sketch = led_s["PU"].entry("moments").nbytes
    assert sketch * 4 <= dense, (n_enc, dense, sketch)
    assert (led_s["PU"].pool_bytes("bram")
            < led_d["PU"].pool_bytes("bram"))
    rep = budget_report(led_s)
    assert rep["fits_bram"] and rep["fits_uram"] and rep["fits"]


def test_sketched_ledger_follows_fallback_predicate():
    """When sketch_pu_fits rejects the requested sketch (absurd width),
    eval_shape-init falls back to dense state and the ledger must charge
    EXACTLY like sketched=False — the ledger and the op share the decision
    by construction."""
    cfg = config_n(2)
    led_fb = training_step_ledger(cfg, "adamw", batch=BATCH, seq=SEQ,
                                  sketched=True, sketch_width=2 ** 22)
    led_d = training_step_ledger(cfg, "adamw", batch=BATCH, seq=SEQ)
    for row in ("moments", "kernel_vmem", "grads", "params"):
        assert (led_fb["PU"].entry(row).nbytes
                == led_d["PU"].entry(row).nbytes)
    assert "sketch" not in led_fb["PU"].entry("moments").note


def test_sketched_state_matches_optimizer_init():
    """The ledger's moment bytes equal the bytes of the REAL optimizer
    state the training step would carry (minus the step scalar) — the
    eval_shape contract, now including sketch buffers."""
    from repro.optim import adamw

    cfg = config_n(2)
    params = _abstract_params(cfg)
    opt = adamw(1e-3, sketched=True)
    state = jax.eval_shape(opt.init, params)
    assert "vs" in state
    state_bytes = sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                      for x in jax.tree.leaves(state)) - 4
    led = training_step_ledger(cfg, "adamw", batch=BATCH, seq=SEQ,
                               sketched=True)
    assert led["PU"].entry("moments").nbytes == state_bytes


# ---------------------------------------------------------------------------
# DECODE stage (serving): paged-KV ledger.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_enc", [2, 4, 6])
def test_decode_ledger_fits_envelope(n_enc):
    """Acceptance: every shipped ATIS config serves inside the 6 MB BRAM +
    22.5 MB URAM envelope at the paper-scale serving point (4 slots,
    64-token contexts, 32-row pages) — the row bench_decode gates on."""
    from repro.core.memory_ledger import decode_ledger_rows

    cfg = config_n(n_enc).with_tt(flow="kernel")
    rows = dict((n, v) for n, v, _ in decode_ledger_rows(
        cfg, "x", batch=4, max_len=64, page_size=32, fused=True))
    assert rows["x/fits"] == 1.0
    assert rows["x/DECODE_mb"] > 0


def test_decode_kv_row_matches_engine_allocator():
    """The kv_pages row is sized by the SAME layout the engine allocates:
    sum over window groups of kv_pool_bytes at max_pages_per_request —
    checked on a hybrid (global + attn_local) config where the two groups
    genuinely differ."""
    import dataclasses

    from repro.core.memory_ledger import decode_step_ledger
    from repro.runtime.decode_engine import _layout
    from repro.runtime.kv_cache import kv_pool_bytes, max_pages_per_request

    cfg = get_config("llama3-8b").scaled_down()
    cfg = dataclasses.replace(cfg, hybrid_pattern=("attn", "attn_local"),
                              window=8)
    B, max_len, page = 3, 48, 4
    led = decode_step_ledger(cfg, batch=B, max_len=max_len, page_size=page)
    n_cycles, _, _, n_pat, n_tail, windows = _layout(cfg)
    assert set(windows.values()) == {None, 8}
    expect = 0
    it = jnp.dtype(cfg.dtype).itemsize
    for gid, window in windows.items():
        n_layers = n_cycles * n_pat.get(gid, 0) + n_tail.get(gid, 0)
        np_max = max_pages_per_request(max_len, page, window)
        expect += kv_pool_bytes(n_layers, 1 + B * np_max, cfg.n_kv_heads,
                                page, cfg.d_head, it)
    assert led.entry("kv_pages").nbytes == expect
    # the windowed group's table is narrower than the global one
    assert (max_pages_per_request(max_len, page, 8)
            < max_pages_per_request(max_len, page, None))


def test_decode_kernel_rows_are_chooser_derived():
    """DECODE kernel-VMEM rows come from the same sizing helpers the ops
    dispatch gates on, and stay inside the URAM envelope."""
    from repro.core.memory_ledger import decode_step_ledger
    from repro.kernels.flash_decode import decode_attn_stage_vmem_bytes

    cfg = config_n(2).with_tt(flow="kernel")
    page = 32
    led = decode_step_ledger(cfg, batch=4, max_len=64, page_size=page)
    it = jnp.dtype(cfg.dtype).itemsize
    G = cfg.n_heads // cfg.n_kv_heads
    assert led.entry("attn_kernel_vmem").nbytes == \
        decode_attn_stage_vmem_bytes(G, cfg.d_head, page, it, fused=True)
    for row in ("attn_kernel_vmem", "kernel_vmem", "ffn_kernel_vmem"):
        assert led.entry(row).nbytes <= URAM_BUDGET_BYTES
    # without the megakernel the hidden column rides URAM...
    assert led.entry("ffn_kernel_vmem").nbytes == 0
    assert led.entry("ffn_hidden").nbytes > 0
    # ...with it, the hidden state is VMEM-resident and the row flips
    led_f = decode_step_ledger(cfg.with_fused_ffn(), batch=4, max_len=64,
                               page_size=page)
    assert led_f.entry("ffn_kernel_vmem").nbytes > 0
    assert led_f.entry("ffn_hidden").nbytes == 0


def test_decode_ledger_rejects_non_attention_families():
    from repro.core.memory_ledger import decode_step_ledger

    cfg = get_config("mamba2-130m").scaled_down()
    with pytest.raises(ValueError):
        decode_step_ledger(cfg)
