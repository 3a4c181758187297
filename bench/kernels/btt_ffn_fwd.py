"""``btt_ffn_fwd``: a TT FFN block's forward; operands ``x`` and the
``(B, A)`` pair of up, (gate,) down, padded to hardware tiles, and grouped
under ``vmap`` as ``btt_linear``'s."""
from bench.work import tt_ffn
from bench.workcount import (fit_matrix, groups, grouped, itemsize, mid_rank,
                             tokens)


def shape(call, ctx, lead: int):
    """``lead`` operands (x, or x and gy) come before the pairs."""
    G, ops = groups(call)
    config = ctx["config"]
    K = tokens(ctx, ops[0][0])
    f, d = fit_matrix(ctx, ops[lead + 1][0], ops[0][1])
    ranks = [mid_rank(config, f, d), mid_rank(config, d, f)]
    if (len(ops) - lead) // 2 == 3:
        ranks.append(mid_rank(config, f, d))
    return G, K, d, f, ranks, itemsize(config)


def work(call, ctx):
    G, K, d, f, ranks, item = shape(call, ctx, 1)
    return grouped(G, (tt_ffn.forward(K, d, f, ranks, item)[0],
                       *tt_ffn.forward_bytes(K, d, f, ranks, item)))
