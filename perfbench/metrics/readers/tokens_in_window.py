"""Tokens per second of the served system over the window: generated tokens
stamped inside it plus, with ``prompt`` true, prompt tokens prefilled inside it,
edges prorated (arith.tokens_in_window), over the window's length. Without the
prompt tokens the rate is far less lumpy (a prefill credits hundreds of tokens
in a second or two), so it stands beside the end-to-end metric as the steadier
reading."""

from perfbench import arith


def read(ctx, prompt=True):
    t0, t1 = ctx.window
    if prompt:
        n = arith.tokens_in_window(ctx.recs, t0, t1)
    else:
        n = sum(1 for r in ctx.recs for t in r.t_emissions if t0 <= t < t1)
    return n / (t1 - t0) if n > 0 else None
