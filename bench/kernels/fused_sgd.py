"""``fused_sgd``: the SGD update of every parameter, shared evenly among
the step's calls of the kernel."""
from bench.work import update
from bench.workcount import itemsize


def share(call, ctx, optimizer: str):
    calls = sum(1 for c in ctx["calls"].values()
                if c["kernel"] == call["kernel"] and not c["remat"])
    n = ctx["params"] // max(calls, 1)
    return (update.work(optimizer, n, itemsize(ctx["config"]))[0],
            *update.work_bytes(optimizer, n, itemsize(ctx["config"])))


def work(call, ctx):
    return share(call, ctx, "sgd")
