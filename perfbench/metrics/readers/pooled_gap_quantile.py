"""A quantile of all gaps between consecutive emissions of all requests,
pooled: a gap belongs to the window if the token that ends it was emitted in
it. Thousands of readings a window."""

from perfbench import arith


def read(ctx, q):
    return arith.quantile(arith.pooled_gaps(ctx.recs, *ctx.window), float(q))
