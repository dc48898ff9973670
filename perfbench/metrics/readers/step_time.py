"""A quantile of the host-clock time of the runner's calls of one kind
(``train_batch`` with the wait for the loss; ``srv.step``) inside the window."""

from perfbench import arith


def read(ctx, kind, q):
    return arith.quantile([s.t1 - s.t0 for s in ctx.steps_in(ctx.window, kind)], float(q))
