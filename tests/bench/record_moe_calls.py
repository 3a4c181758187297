#!/usr/bin/env python3
"""Record the kernel calls of a mixture-of-experts train step that
``test_bench_groups.py`` counts.

    JAX_PLATFORMS=cpu python3 tests/bench/record_moe_calls.py OUT.json

Needs no chip: compiles ``repro``'s train step for a described TPU v5e,
ahead of time, for the scaled-down ``qwen2-moe-a2.7b`` (TT rank 8, the
kernel path, AdamW, batch 2 x 64), once with the fused FFN kernel (float32,
``btt_ffn_*`` under ``vmap`` over the experts) and once without it
(bfloat16, ``btt_linear`` and ``btt_backward`` under ``vmap``; Mosaic
refuses the fused FFN kernel in bfloat16 at these widths).  Writes each
compile's kernel calls (``bench.workcount.kernel_calls``), its parameter
layout and its model settings as JSON.
"""
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH, BATCH, SEQ, RANK = "qwen2-moe-a2.7b", 2, 64, 8
VARIANTS = {"fused_ffn": ("float32", True), "unfused": ("bfloat16", False)}


def record(dtype: str, fused_ffn: bool) -> dict:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.weights import describe
    from bench.workcount import kernel_calls
    from repro.configs import get_config
    from repro.launch.steps import make_train_step
    from repro.models.transformer import init_params
    from repro.optim import adamw, warmup_cosine

    cfg = get_config(ARCH).scaled_down().with_tt(
        mode="tt", rank=RANK, embed_rank=RANK, flow="kernel")
    cfg = dataclasses.replace(cfg, fused_attn=True, fused_ffn=fused_ffn,
                              dtype=dtype)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    opt = adamw(warmup_cosine(1e-3, 10, 100), fused=True)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((BATCH, SEQ), dt, sharding=chip)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("mask", jnp.float32))}
    text = jax.jit(make_train_step(cfg, opt)).lower(
        on_chip(params), on_chip(state), batch).compile().as_text()
    return {"calls": kernel_calls(text), "layout": describe(params),
            "model": {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
                      "d_head": cfg.d_head, "causal": cfg.causal,
                      "dtype": dtype, "fused_ffn": fused_ffn,
                      "moe": dataclasses.asdict(cfg.moe)},
            "tt": {"rank": RANK, "clamp_ranks": cfg.tt.clamp_ranks},
            "traffic": {"batch": BATCH, "seq": SEQ}}


def main(out: str) -> None:
    # A compile for a described chip cannot be read back from the
    # persistent cache; the kernels take their TPU path only where JAX's
    # backend says TPU, so this script says so while it compiles.
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"
    data = {name: record(*v) for name, v in VARIANTS.items()}
    with open(out, "w") as f:
        json.dump(data, f)
    for name, d in data.items():
        print(name, sorted({c["kernel"] for c in d["calls"].values()}))


if __name__ == "__main__":
    main(sys.argv[1])
