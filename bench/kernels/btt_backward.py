"""``btt_backward``: a TT linear's backward; operands ``x, gy, B, A``,
padded and grouped as ``btt_linear``'s."""
from bench.kernels.btt_linear import shape
from bench.work import tt_linear
from bench.workcount import grouped


def work(call, ctx):
    G, K, M, N, r, item = shape(call, ctx)
    return grouped(G, (tt_linear.backward(K, M, N, r, item)[0],
                       *tt_linear.backward_bytes(K, M, N, r, item)))
