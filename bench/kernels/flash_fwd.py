"""``flash_fwd``: attention's forward over the traffic's batch and
sequence at the model's heads."""
from bench.work import attention
from bench.workcount import itemsize


def shape(ctx):
    m, t = ctx["config"]["model"], ctx["traffic"]
    return (t["batch"], m["n_heads"], m["n_kv_heads"], t["seq"], m["d_head"])


def work(call, ctx):
    s, item = shape(ctx), itemsize(ctx["config"])
    return (attention.forward(*s, ctx["config"]["model"]["causal"], item)[0],
            *attention.forward_bytes(*s, item))
