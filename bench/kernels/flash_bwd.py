"""``flash_bwd``: attention's backward, as ``flash_fwd``'s shapes."""
from bench.kernels.flash_fwd import shape
from bench.work import attention
from bench.workcount import itemsize


def work(call, ctx):
    s, item = shape(ctx), itemsize(ctx["config"])
    return (attention.backward(*s, ctx["config"]["model"]["causal"], item)[0],
            *attention.backward_bytes(*s, item))
