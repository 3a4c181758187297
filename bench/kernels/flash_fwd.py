"""``flash_fwd``: attention's forward over the traffic's batch and
sequence at the model's heads: queries and keys of ``d_head``, values of
``d_head_v`` where the model gives one, else ``d_head``."""
from bench.work import attention
from bench.workcount import itemsize


def shape(ctx):
    """``(B, H, KV, S, D, Dv)``."""
    m, t = ctx["config"]["model"], ctx["traffic"]
    return (t["batch"], m["n_heads"], m["n_kv_heads"], t["seq"], m["d_head"],
            m.get("d_head_v", m["d_head"]))


def work(call, ctx):
    B, H, KV, S, D, Dv = shape(ctx)
    causal, item = ctx["config"]["model"]["causal"], itemsize(ctx["config"])
    return (attention.forward(B, H, KV, S, D, causal, item, Dv)[0],
            *attention.forward_bytes(B, H, KV, S, D, item, Dv))
