"""``flash_bwd``: attention's backward, as ``flash_fwd``'s shapes."""
from bench.kernels.flash_fwd import shape
from bench.work import attention
from bench.workcount import itemsize


def work(call, ctx):
    B, H, KV, S, D, Dv = shape(ctx)
    causal, item = ctx["config"]["model"]["causal"], itemsize(ctx["config"])
    return (attention.backward(B, H, KV, S, D, causal, item, Dv)[0],
            *attention.backward_bytes(B, H, KV, S, D, item, Dv))
