"""``btt_linear``: a TT linear's forward ``y = (x B^T) A^T``; operands
``x (K, N), B (r, N), A (M, r)``, padded to hardware tiles."""
from bench.work import tt_linear
from bench.workcount import fit_width, itemsize, mid_rank, tokens


def shape(call, ctx):
    ops, config = call["operands"], ctx["config"]
    K = tokens(ctx, ops[0][0])
    N, M = fit_width(config, ops[0][1]), fit_width(config, ops[-1][0])
    return K, M, N, mid_rank(config, M, N), itemsize(config)


def work(call, ctx):
    K, M, N, r, item = shape(call, ctx)
    return (tt_linear.forward(K, M, N, r, item)[0],
            *tt_linear.forward_bytes(K, M, N, r, item))
