"""``fused_adamw``: the AdamW update of every parameter, shared evenly
among the step's calls of the kernel."""
from bench.kernels.fused_sgd import share


def work(call, ctx):
    return share(call, ctx, "adamw")
