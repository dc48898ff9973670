"""The one-token paged attention kernel's share of its roofline for a family
whose attention layers are ONE IN ``full_attention_interval`` (the others keep
a recurrent state and read no key), over the traced part of the window. The
keys each decode step read are the program's own count: ``ds.serve.decode.
dispatch``'s ``attended`` is the slots' contexts, the token the step writes
included, and each attention layer's kernel call reads them once. Costs:
perfbench/kernel_costs_qwen3_next.py. A program without the span or the
attribute gives nothing."""

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_qwen3_next as kq
from perfbench import program_spans


def read(ctx, pattern):
    tr = ctx.trace
    if tr is None or ctx.traced is None:
        return None
    kernel_s = tr.seconds_matching(pattern)
    recs = program_spans.records_in(ctx.traced)
    steps = [r[3] for r in recs or () if r[0] == "ds.serve.decode.dispatch" and "attended" in r[3]]
    if kernel_s <= 0 or not steps:
        return None
    c = ctx.config
    L = kq.kinds(c).count("attn")
    itemsize = 2 if c["dtype"] == "bfloat16" else 4
    f, b = kq.paged_decode_keys(
        L * sum(int(s["attended"]) for s in steps), int(c["num_key_value_heads"]), int(c["num_attention_heads"]),
        int(c["head_dim"]), itemsize, L * sum(int(s["active"]) for s in steps),
    )
    return 100.0 * kc.min_seconds(f, b, ctx.peak)[0] / kernel_s
