"""Scaled-down copies of the benchmark's cells, for tests on the CPU.

Each keeps its configuration's structure (encoder or causal decoder, tied
TTM head or TT head, GELU or SwiGLU, f32 or bf16, SGD or AdamW) at widths
that interpret-mode kernels run in seconds."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

_cell = spec.cell

SMALL = {
    "atis6-tt.b1s32": ({"num_layers": 2, "d_model": 128, "n_heads": 2,
                        "n_kv_heads": 2, "d_head": 64, "d_ff": 128,
                        "vocab_size": 256}, {"batch": 2, "seq": 16}, None),
    "granite8b-tt.b1s4096": ({"num_layers": 2, "d_model": 128, "n_heads": 4,
                              "n_kv_heads": 2, "d_head": 32, "d_ff": 256,
                              "vocab_size": 512}, {"batch": 1, "seq": 64}, 8),
}


def tiny_cell(name: str, float32: bool = False) -> dict:
    """``name`` scaled down; with ``float32`` its dtype is float32, so that
    the program on the CPU computes exactly what the reference does."""
    widths, traffic, rank = SMALL[name]
    c = copy.deepcopy(_cell(name))
    if float32:
        widths = dict(widths, dtype="float32")
    c["config"]["model"].update(widths)
    c["config"]["replace"] = dict(c["config"]["replace"], **widths)
    if rank is not None:
        c["config"]["tt"].update(rank=rank, embed_rank=rank)
        c["config"]["build"]["tt_rank"] = rank
    c["traffic"].update(traffic)
    return c


CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


MOE_ARCHS = ("qwen2-moe-a2.7b", "llama4-maverick-400b-a17b")


def moe_config(arch: str, rank: int = 16) -> dict:
    """A configuration file's contents for ``arch`` (one of the program's
    mixture-of-experts archs) at the program's own scaled-down widths, TT
    compressed on the kernel path, its ``moe`` section nested as a file
    states it."""
    from repro.configs import get_config

    small = get_config(arch).scaled_down()
    moe = {"num_experts": small.moe.num_experts, "d_expert": small.moe.d_expert,
           "shared_d_ff": small.moe.shared_d_ff}
    widths = {"num_layers": small.num_layers, "d_model": small.d_model,
              "n_heads": small.n_heads, "n_kv_heads": small.n_kv_heads,
              "d_head": small.d_head, "d_ff": small.d_ff,
              "vocab_size": small.vocab_size, "dtype": "float32", "moe": moe}
    return {"name": arch, "arch": arch, "family": "dense_transformer",
            "build": {"tt": True, "tt_rank": rank, "kernel_flow": True,
                      "fused_attn": True, "fused_ffn": True, "fused": True},
            "replace": widths,
            "model": dict(widths, moe=dict(moe, top_k=small.moe.top_k,
                                           every=small.moe.every)),
            "tt": {"mode": "tt", "rank": rank, "flow": "kernel",
                   "scope": ["attn", "ffn", "embed", "head"]}}
