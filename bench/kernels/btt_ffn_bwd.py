"""``btt_ffn_bwd``: a TT FFN block's backward; operands ``x, gy`` and the
pairs, as ``btt_ffn_fwd``'s."""
from bench.kernels.btt_ffn_fwd import shape
from bench.work import tt_ffn
from bench.workcount import grouped


def work(call, ctx):
    G, K, d, f, ranks, item = shape(call, ctx, 2)
    return grouped(G, (tt_ffn.backward(K, d, f, ranks, item)[0],
                       *tt_ffn.backward_bytes(K, d, f, ranks, item)))
