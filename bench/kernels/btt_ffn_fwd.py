"""``btt_ffn_fwd``: a TT FFN block's forward; operands ``x`` and the
``(B, A)`` pair of up, (gate,) down, padded to hardware tiles."""
from bench.work import tt_ffn
from bench.workcount import fit_width, itemsize, mid_rank, tokens


def shape(call, ctx, lead: int):
    """``lead`` operands (x, or x and gy) come before the pairs."""
    ops, config = call["operands"], ctx["config"]
    K = tokens(ctx, ops[0][0])
    d, f = fit_width(config, ops[0][1]), fit_width(config, ops[lead + 1][0])
    ranks = [mid_rank(config, f, d), mid_rank(config, d, f)]
    if (len(ops) - lead) // 2 == 3:
        ranks.append(mid_rank(config, f, d))
    return K, d, f, ranks, itemsize(config)


def work(call, ctx):
    K, d, f, ranks, item = shape(call, ctx, 1)
    return (tt_ffn.forward(K, d, f, ranks, item)[0],
            *tt_ffn.forward_bytes(K, d, f, ranks, item))
