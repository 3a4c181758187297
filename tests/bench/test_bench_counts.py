"""Kernel counts beyond the dense cells' shapes: matrices matched whole
against the layout's TT matrices, calls grouped under ``vmap`` (one call
over a stack of experts), and attention whose values are narrower than its
queries and keys (latent attention), each checked by hand; and the calls
the cells' recorded step makes, counted as the harness counted them
before any of this."""
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from bench_tiny import MOE_ARCHS, moe_config  # noqa: E402

from bench import spec  # noqa: E402
from bench.weights import matrix_sides  # noqa: E402
from bench.work import attention  # noqa: E402
from bench.workcount import (call_work, context, fit_matrix, groups,  # noqa: E402
                             on_chip_bytes, step_work)

# (FLOPs, bytes, bytes on chip) of every kernel call of the two-step
# ``atis6-tt.b1s32`` traces recorded on a v5e, as the harness counted them
# when it took matrix sides from the model's widths and read every call as
# one group.
ATIS_B1S32 = {
    "btt_backward.36": (2359296, 442368, 442368),
    "btt_backward.37": (2359296, 442368, 442368),
    "btt_backward.38": (2359296, 442368, 442368),
    "btt_backward.39": (2359296, 442368, 442368),
    "btt_ffn_bwd.9": (4718592, 589824, 589824),
    "btt_ffn_fwd.7": (2359296, 344064, 344064),
    "btt_linear.64": (1179648, 270336, 270336),
    "btt_linear.65": (1179648, 270336, 270336),
    "btt_linear.66": (1179648, 270336, 270336),
    "btt_linear.67": (1179648, 270336, 270336),
    "btt_linear.68": (0, 0, 0),
    "btt_linear.69": (0, 0, 0),
    "btt_linear.70": (0, 0, 0),
    "btt_linear.71": (0, 0, 0),
    "flash_bwd.9": (6291456, 786432, 786432),
    "flash_fwd.16": (3145728, 393216, 393216),
    "flash_fwd.17": (0, 0, 0),
    "fused_sgd.1": (667008, 2668032, 2668032),
}


def _load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def _record(cell_name, recorded):
    cell = spec.cell(cell_name)
    lay = [(p, tuple(s), d) for p, s, d in recorded["layout"]]
    return {"config": cell["config"], "traffic": cell["traffic"],
            "work": step_work(cell["config"], cell["traffic"], lay),
            "calls": recorded["calls"]}


def _counted(record):
    ctx = context(record)
    return {n: (*call_work(c, ctx), on_chip_bytes(c, ctx))
            for n, c in record["calls"].items()}


@pytest.mark.parametrize("fixture", ["trace_small.json", "trace_scopes.json"])
def test_recorded_calls_count_as_before(fixture):
    record = _record("atis6-tt.b1s32", _load(fixture))
    assert _counted(record) == ATIS_B1S32
    assert record["work"]["step_flops"] == 353160576


CELL_CALLS = _load("cell_calls.json")


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_cell_calls_count_as_before(cell):
    """The compiled step's kernel calls of three more cells, read on a v5e
    (``bench.program.Program(...).calls``), with the counts the harness
    gave them when it took matrix sides from the model's widths and read
    every call as one group.  Granite's head backward runs ``btt_linear``
    on the head's transposed factors (x of 49,152 columns)."""
    rec = CELL_CALLS[cell]
    record = _record(cell, rec)
    assert _counted(record) == {n: tuple(v) for n, v in rec["counts"].items()}
    assert record["work"]["step_flops"] == rec["step_flops"]


# --- latent attention: values narrower than queries and keys -----------------

def test_latent_attention_counts_by_hand():
    """B 1, H = KV = 16, S 8192 causal, queries and keys 192 wide (128 + 64
    rotary), values 128, bf16."""
    pairs = 8192 * 8193 // 2
    q = k = 16 * 8192 * 192 * 2
    v = o = 16 * 8192 * 128 * 2
    fl, by = attention.forward(1, 16, 16, 8192, 192, True, 2, Dv=128)
    assert fl == 2 * 16 * pairs * (192 + 128) == 343_639_326_720
    assert by == q + k + v + o == 167_772_160
    fl_b, by_b = attention.backward(1, 16, 16, 8192, 192, True, 2, Dv=128)
    assert fl_b == 2 * fl
    assert by_b == (q + o + o + k + v) + (q + k + v) == 335_544_320
    # One width for all three would count 20% more FLOPs and 50% more v, o.
    assert attention.forward(1, 16, 16, 8192, 192, True, 2)[0] == 1.2 * fl


def test_flash_counts_read_the_value_width():
    ctx = {"config": {"model": {"n_heads": 16, "n_kv_heads": 16, "d_head": 192,
                                "d_head_v": 128, "causal": True,
                                "dtype": "bfloat16"}},
           "traffic": {"batch": 1, "seq": 8192}}
    for kernel, fn in (("flash_fwd", attention.forward),
                       ("flash_bwd", attention.backward)):
        call = {"kernel": kernel, "operands": [], "remat": False}
        assert call_work(call, ctx) == fn(1, 16, 16, 8192, 192, True, 2, Dv=128)


def test_reference_attention_returns_the_value_width():
    import jax
    import jax.numpy as jnp

    from bench.reference import attention as ref_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, 8, 4, 24))
    k = jax.random.normal(kk, (1, 8, 4, 24))
    v = jax.random.normal(kv, (1, 8, 4, 16))
    out = ref_attention(q, k, v, True, lambda x: x)
    assert out.shape == (1, 8, 4 * 16)
    s = jnp.einsum("bqhd,bchd->bhqc", q, k) / math.sqrt(24)
    s = jnp.where(jnp.tril(jnp.ones((8, 8), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqc,bchd->bqhd", jax.nn.softmax(s, -1), v)
    assert jnp.allclose(out, want.reshape(1, 8, 64), atol=1e-5)


# --- calls grouped under vmap --------------------------------------------------

MOE = _load("moe_calls.json")


def _moe_ctx(variant):
    rec = MOE[variant]
    lay = [(p, tuple(s), d) for p, s, d in rec["layout"]]
    return {"config": {"model": rec["model"], "tt": rec["tt"]},
            "traffic": rec["traffic"], "params": 1000,
            "sides": matrix_sides(lay), "calls": rec["calls"]}


def test_grouped_btt_linear_by_hand():
    """The expert up-projection of the recorded ``unfused`` step: 64 experts
    (4 routed, padded to 64), 160 capacity rows each, capped at the
    traffic's 128 tokens; (out, in) = (128, 256), rank 8, bf16."""
    call = {"kernel": "btt_linear", "remat": False,
            "operands": [(64, 160, 512), (64, 128, 512), (64, 128, 128)]}
    K, M, N, r = 128, 128, 256, 8
    flops = 64 * 2 * K * r * (M + N)
    nbytes = 64 * (K * N + r * N + M * r + K * M) * 2
    assert (flops, nbytes) == (50_331_648, 6_684_672)
    assert call_work(call, _moe_ctx("unfused")) == (flops, nbytes)


@pytest.mark.parametrize("variant", sorted(MOE))
def test_recorded_moe_calls_count_by_group(variant):
    """Every kernel call of a mixture-of-experts step compiled for a v5e
    (``record_moe_calls.py``) is counted; a call over a stack of experts
    counts as its experts, each at its own rows."""
    ctx = _moe_ctx(variant)
    grouped = 0
    for name, call in ctx["calls"].items():
        got = call_work(call, ctx)
        if not call["kernel"].startswith("btt"):
            continue
        G, ops = groups(call)
        if G > 1:
            grouped += 1
            one = call_work(dict(call, operands=ops), ctx)
            assert got == (G * one[0], G * one[1]), name
            assert G == MOE[variant]["model"]["moe"]["pad_experts_to"]
    assert grouped >= 3


def test_unlike_group_axes_are_refused():
    call = {"kernel": "btt_linear", "remat": False,
            "operands": [(64, 160, 512), (128, 512), (128, 128)]}
    with pytest.raises(ValueError, match="count of its own"):
        groups(call)


# --- matrices matched whole -----------------------------------------------------

def _up(x, m):
    return (x + m - 1) // m * m


# Moonlight-16B-A3B's TT matrices (out, in) at its published widths: MLA's
# query (16 x 192), KV down-projection (512 latent + 64 rotary) and
# up-projection (16 x (128 + 128)) and output, the dense layer's SwiGLU,
# the routed experts' (1408) and the two shared experts' (2816), the head.
MOONLIGHT = {"q": (3072, 2048), "kv_a": (576, 2048), "kv_b": (4096, 512),
             "o": (2048, 2048), "dense_up": (11264, 2048),
             "dense_down": (2048, 11264), "expert_up": (1408, 2048),
             "expert_down": (2048, 1408), "shared_up": (2816, 2048),
             "shared_down": (2048, 2816), "head": (163840, 2048)}


def _cell_sides(name):
    from bench.program import layout

    return matrix_sides(layout(spec.load_config(name)))


@pytest.mark.parametrize("model", ["moonlight", "atis6-tt", "granite8b-tt"])
def test_every_matrix_fits_as_the_kernels_pad_it(model):
    """``btt_linear`` pads rows to 128 and the in-width to 512 lanes,
    ``btt_backward`` the in-width to 128 up to 1,024 and to 512 above, the
    fused FFN kernels every side to 128.  Matched side by side, Moonlight's
    shared down-projection (in 2,816, padded to 3,072) would read as the
    query's 3,072; and a transpose is taken only where no matrix fits as
    it stands, since the query's transpose (2,048 x 3,072) fits it too."""
    sides = MOONLIGHT if model == "moonlight" else _cell_sides(model)
    ctx = {"sides": sides}
    for out_dim, in_dim in sides.values():
        bwd_in = _up(in_dim, 128) if in_dim <= 1024 else _up(in_dim, 512)
        for cols in (in_dim, _up(in_dim, 512), bwd_in, _up(in_dim, 128)):
            assert fit_matrix(ctx, _up(out_dim, 128), cols) == (out_dim, in_dim)
    # The head's backward: btt_linear on the transposed factors.
    head = sides.get("head") or sides.get("['head']")
    if head is not None:
        out_dim, in_dim = head
        assert fit_matrix(ctx, _up(in_dim, 128), _up(out_dim, 512)) == (in_dim, out_dim)


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_every_scaled_down_moe_matrix_fits(arch):
    """The registered MoE archs at the program's scaled-down widths: every
    TT matrix (attention, routed and shared experts, head) is
    found under every kernel's padding, and is itself at 128-row tiles.
    (At these widths a 512-lane pad can span two in-widths; see PERF.md.)"""
    from bench.program import layout

    sides = matrix_sides(layout(moe_config(arch)))
    ctx = {"sides": sides}
    for out_dim, in_dim in sides.values():
        assert fit_matrix(ctx, _up(out_dim, 128), _up(in_dim, 128)) == (out_dim, in_dim)
        fit_matrix(ctx, _up(out_dim, 128), _up(in_dim, 512))


def test_no_matrix_fits_is_an_error():
    with pytest.raises(ValueError, match="no TT matrix"):
        fit_matrix({"sides": {"w": (256, 512)}}, 128, 512)
