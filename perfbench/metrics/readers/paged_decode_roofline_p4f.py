"""The one-token paged attention kernel's share of its roofline for a family
whose cached head is a PAIR of published heads and whose sub-blocks are of
several kinds (window rings, one paged layer, cross layers that read it again,
and sub-blocks that read no key), over the traced part of the window. The keys
each decode step read are the program's own count: ``ds.serve.decode.
dispatch``'s ``attended`` is what a sub-block reads averaged over ALL
sub-blocks (0 for a state-space mixer or a memory unit), so times their number
it is the keys of the step's kernel calls; the queries are the attending
sub-blocks' (perfbench/kernel_costs_phi4flash.py). A program without the span
or the attribute gives nothing."""

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_phi4flash as kp
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
    kv, heads, lanes = kp.pair_heads(c)
    itemsize = 2 if c["dtype"] == "bfloat16" else 4
    f, b = kp.paged_decode_keys(
        int(c["num_hidden_layers"]) * sum(int(s["attended"]) for s in steps), kv, heads, lanes, itemsize,
        kp.attending(c) * sum(int(s["active"]) for s in steps),
    )
    return 100.0 * kc.min_seconds(f, b, ctx.peak)[0] / kernel_s
