"""``btt_backward``: a TT linear's backward; operands ``x, gy, B, A``,
padded as ``btt_linear``'s."""
from bench.kernels.btt_linear import shape
from bench.work import tt_linear


def work(call, ctx):
    K, M, N, r, item = shape(call, ctx)
    return (tt_linear.backward(K, M, N, r, item)[0],
            *tt_linear.backward_bytes(K, M, N, r, item))
