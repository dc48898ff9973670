"""The flash attention kernels' share of their roofline over the traced part
of the window. Per layer and step the forward kernel runs ``forward_calls``
times (once, and once more where full rematerialisation re-runs the block) and
the backward once; each call's operations and bytes come from its shapes
(perfbench/kernel_costs.py)."""

from perfbench import kernel_costs as kc


def read(ctx, pattern, forward_calls):
    tr = ctx.trace
    if tr is None or ctx.traced is None:
        return None
    kernel_s = tr.seconds_matching(pattern)
    n_steps = len(ctx.steps_in(ctx.traced, "train_batch"))
    if kernel_s <= 0 or n_steps == 0:
        return None
    c = ctx.config
    micro = int(c["engine"]["train_micro_batch_size_per_gpu"])
    head_dim = c["n_embd"] // c["n_head"]
    fwd = kc.flash_causal(micro, c["seq"], c["n_head"], head_dim, 2, backward=False)
    bwd = kc.flash_causal(micro, c["seq"], c["n_head"], head_dim, 2, backward=True)
    per_layer = int(forward_calls) * kc.min_seconds(*fwd, ctx.peak)[0] + kc.min_seconds(*bwd, ctx.peak)[0]
    return 100.0 * n_steps * c["n_layer"] * per_layer / kernel_s
