"""Tokens per second per chip: whole steps inside the window, over the time
between the first and the last step boundary inside it, over the chips."""

from perfbench import arith


def read(ctx):
    rate, _ = arith.train_rate(ctx.step_ends, ctx.tokens_per_step, *ctx.window)
    return None if rate is None else rate / ctx.chips
