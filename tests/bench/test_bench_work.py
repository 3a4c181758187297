"""Work counts of the benchmark, checked against counts written out by
hand at the paper's 768x768 rank-12 layer (batch 1, seq 32)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.work import attention, lm_head, tt_ffn, tt_linear, ttm_embed, update  # noqa: E402

K, D, R = 32, 768, 12
RANKS = (1, 12, 12, 12, 12, 12, 1)
FACTORS = (12, 8, 8)


def test_mul_btt_is_paper_eq20():
    # builds: k=0: 12*12*(8*8) + 12*12*(12*8); k=1: 12*12*768 + 12*12*768
    builds = 9216 + 13824 + 110592 + 110592
    assert tt_linear.mul_btt(RANKS, FACTORS, FACTORS, K) == builds + 32 * 12 * 1536


@pytest.mark.parametrize("fn,flops,nbytes", [
    # 2 K r (M + N); x, B, A, y at 4 bytes
    (tt_linear.forward, 2 * 32 * 12 * 1536, (32 * 768 + 12 * 768 + 768 * 12 + 32 * 768) * 4),
    # twice the forward; x, gy, gx and A, B with their gradients
    (tt_linear.backward, 4 * 32 * 12 * 1536, (3 * 32 * 768 + 2 * (2 * 12 * 768)) * 4),
])
def test_tt_linear(fn, flops, nbytes):
    assert fn(K, D, D, R, 4) == (flops, nbytes)


def test_tt_ffn_counts_the_block_not_its_hidden_state():
    fl, by = tt_ffn.forward(K, D, D, [R, R], 4)
    assert fl == 2 * 2 * 32 * 12 * 1536
    assert by == (2 * 32 * 768 + 2 * 12 * 1536) * 4
    fl_b, by_b = tt_ffn.backward(K, D, D, [R, R], 4)
    assert fl_b == 2 * fl
    assert by_b == (3 * 32 * 768 + 4 * 12 * 1536) * 4


@pytest.mark.parametrize("causal,pairs", [(False, 32 * 32), (True, 32 * 33 // 2)])
def test_attention(causal, pairs):
    fl, by = attention.forward(1, 12, 12, 32, 64, causal, 4)
    assert fl == 4 * 12 * pairs * 64
    assert by == 32 * 64 * 4 * 12 * 4
    fl_b, by_b = attention.backward(1, 12, 12, 32, 64, causal, 4)
    assert (fl_b, by_b) == (2 * fl, 2 * by)


def test_ttm_embedding_takes_the_cheaper_flow():
    cores = [(1, 16, 12, 30), (30, 8, 8, 30), (30, 8, 8, 1)]
    gather = 32 * 12 * 8 * 30 * 30 + 32 * 96 * 8 * 1 * 30
    build = 128 * 96 * 30 * 30 + 1024 * 768 * 30 * 1
    assert ttm_embed.gather_muls(cores, 32) == gather == 3502080
    assert ttm_embed.build_muls(cores) == build == 34652160
    assert ttm_embed.flops(cores, 32) == 6 * gather


def test_tied_head_over_the_logical_vocabulary():
    assert lm_head.flops(32, 1000, 768) == 3 * 2 * 32 * 1000 * 768


@pytest.mark.parametrize("opt,p_bytes,expect", [
    # p read and written; the gradient, an intermediate, counts nothing
    ("sgd", 4, (2000, 1000 * (4 + 4))),
    ("adamw", 2, (12000, 1000 * (2 + 2 + 4 * 4))),
])
def test_update(opt, p_bytes, expect):
    assert update.work(opt, 1000, p_bytes) == expect


HLO = """\
  %pad.414 = f32[32,1024]{1,0:T(8,128)S(1)} pad(f32[32,768]{1,0} %x, f32[] %c), padding=0_0x0_256
  %pad.415 = f32[128,1024]{1,0:T(8,128)} pad(f32[12,768]{1,0} %b, f32[] %c), padding=0_116x0_256
  %pad.416 = f32[768,128]{1,0:T(8,128)} pad(f32[768,12]{1,0} %a, f32[] %c), padding=0_0x0_116
  %btt_linear.64 = f32[32,768]{1,0:T(8,128)S(1)} custom-call(%pad.414, %pad.415, %pad.416), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[32,1024]{1,0}, f32[128,1024]{1,0}, f32[768,128]{1,0}}, metadata={op_name="jit(train_step)/jvp()/btt_linear/pallas_call"}
  ROOT %btt_linear.65 = f32[32,768]{1,0:T(8,128)} custom-call(%pad.414, %pad.415, %pad.416), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[32,1024]{1,0}, f32[128,1024]{1,0}, f32[768,128]{1,0}}, metadata={op_name="jit(train_step)/transpose(jvp())/rematted_computation/btt_linear/pallas_call"}
"""


def test_kernel_calls_read_where_each_operand_lives():
    from bench.workcount import kernel_calls

    calls = kernel_calls(HLO)
    c = calls["btt_linear.64"]
    assert c["kernel"] == "btt_linear"
    assert c["operands"] == [(32, 1024), (128, 1024), (768, 128)]
    assert c["hbm_in"] == [False, True, True]
    assert c["hbm_out"] == [False]
    assert not c["remat"] and calls["btt_linear.65"]["remat"]


def _ctx(name="atis6-tt.b1s32", calls=None):
    from bench import spec
    from bench.program import layout
    from bench.weights import matrix_sides

    cell = spec.cell(name)
    return {"config": cell["config"], "traffic": cell["traffic"],
            "params": 1000, "sides": matrix_sides(layout(cell["config"])),
            "calls": calls or {}}


def test_call_work_counts_bytes_from_shapes_alone():
    from bench.workcount import call_work, kernel_calls, on_chip_bytes

    calls = kernel_calls(HLO)
    ctx = _ctx(calls=calls)
    x = y = 32 * 768 * 4
    fl, by = call_work(calls["btt_linear.64"], ctx)
    assert fl == 2 * 32 * 12 * 1536
    assert by == x + (12 * 768 + 768 * 12) * 4 + y   # wherever x and y live
    assert on_chip_bytes(calls["btt_linear.64"], ctx) == x + y
    assert call_work(calls["btt_linear.65"], ctx) == (0, 0)
    in_hbm = dict(calls["btt_linear.64"], hbm_in=[True] * 3, hbm_out=[True])
    assert call_work(in_hbm, ctx) == (fl, by)
    assert on_chip_bytes(in_hbm, ctx) == 0


KERNEL_NAMES = ("btt_linear", "btt_backward", "btt_ffn_fwd", "btt_ffn_bwd",
                "flash_fwd", "flash_bwd", "fused_sgd", "fused_adamw")


@pytest.mark.parametrize("kernel", KERNEL_NAMES)
def test_kernel_work_is_found_by_name(kernel):
    from bench import spec

    assert callable(spec.load_module("kernels", kernel).work)


def test_unknown_kernel_has_no_count():
    from bench.workcount import call_work

    call = {"kernel": "no_such_kernel", "operands": [], "remat": False}
    with pytest.raises(KeyError):
        call_work(call, _ctx())


@pytest.mark.parametrize("kernel,calls,n", [("fused_sgd", 1, 1000), ("fused_adamw", 2, 500)])
def test_update_is_shared_among_its_calls(kernel, calls, n):
    from bench.workcount import call_work

    c = {"kernel": kernel, "operands": [], "remat": False}
    ctx = _ctx(calls={f"{kernel}.{i}": c for i in range(calls)})
    opt = "sgd" if kernel == "fused_sgd" else "adamw"
    assert call_work(c, ctx) == update.work(opt, n, 4)


def test_step_flops_come_from_the_model_family():
    from bench import spec
    from bench.workcount import step_work

    cell = spec.cell("atis6-tt.b1s32")
    layout = [("['embed'].cores[0]", (1, 16, 12, 30), "float32"),
              ("['embed'].cores[1]", (30, 8, 8, 30), "float32"),
              ("['embed'].cores[2]", (30, 8, 8, 1), "float32")]
    got = step_work(cell["config"], cell["traffic"], layout)
    # q, k, v, o: forward 2 K r (M + N), backward twice; the FFN's up and
    # down alike; attention over all 32 x 32 pairs, backward twice.
    per_layer = (4 * 3 * 2 * 32 * 12 * 1536 + 2 * 3 * 2 * 32 * 12 * 1536
                 + 3 * 4 * 12 * 32 * 32 * 64)
    want = (6 * per_layer + lm_head.flops(32, 1000, 768)
            + 6 * 3502080 + 2 * got["params"])
    assert got["params"] == 1 * 16 * 12 * 30 + 30 * 8 * 8 * 30 + 30 * 8 * 8 * 1
    assert got["step_flops"] == want
