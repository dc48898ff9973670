"""The paged decode kernel's share of its roofline over the traced part of
the window: the least time the chip could take for the calls that ran (bytes
and operations from their shapes, perfbench/kernel_costs.py, over the peaks)
over the kernel's device time in the trace. One call per layer per decode
step; the context each slot attended comes from the runner's step records."""

from perfbench import kernel_costs as kc


def read(ctx, pattern):
    tr = ctx.trace
    if tr is None or ctx.traced is None:
        return None
    kernel_s = tr.seconds_matching(pattern)
    steps = [s for s in ctx.steps_in(ctx.traced, "srv.step") if s.info.get("decode_tokens", 0) > 0]
    if kernel_s <= 0 or not steps:
        return None
    c = ctx.config
    head_dim = c["n_embd"] // c["n_head"]
    itemsize = 2 if c["dtype"] == "bfloat16" else 4
    least = 0.0
    for s in steps:
        f, b = kc.paged_decode(s.info["attended_tokens"], c["n_head"], head_dim, itemsize, s.info["decode_tokens"])
        least += c["n_layer"] * kc.min_seconds(f, b, ctx.peak)[0]
    return 100.0 * least / kernel_s
