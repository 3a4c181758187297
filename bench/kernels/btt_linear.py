"""``btt_linear``: a TT linear's forward ``y = (x B^T) A^T``; operands
``x (K, N), B (r, N), A (M, r)``, padded to hardware tiles.  Under ``vmap``
(one call over a stack of experts) each operand carries the group axes in
front: ``G`` groups, each counted at its own rows."""
from bench.work import tt_linear
from bench.workcount import (fit_matrix, groups, grouped, itemsize, mid_rank,
                             tokens)


def shape(call, ctx):
    G, ops = groups(call)
    K = tokens(ctx, ops[0][0])
    M, N = fit_matrix(ctx, ops[-1][0], ops[0][1])
    return G, K, M, N, mid_rank(ctx["config"], M, N), itemsize(ctx["config"])


def work(call, ctx):
    G, K, M, N, r, item = shape(call, ctx)
    return grouped(G, (tt_linear.forward(K, M, N, r, item)[0],
                       *tt_linear.forward_bytes(K, M, N, r, item)))
