"""A latent (MLA) attention kernel's share of its roofline over the traced
part of the window: the least time the chip could take for the pairs the
kernel's calls attended (perfbench/kernel_costs_mistral4.py, over the peaks)
over the device time of the operations matching ``pattern``.

The pairs are the program's own count. ``kind`` ``decode``: ``ds.serve.decode.
dispatch``'s ``attended`` (each active slot's cached rows and the one it
writes) a step, one query a slot (``active``), every layer. ``kind``
``chunk``: ``ds.serve.chunk``'s ``attended`` (a chunk call's queries times the
context before it, plus the causal triangle inside it, of the real tokens) and
``tokens``, every layer; a row serves at most a chunk of queries. A program
without the span or the attribute gives nothing."""

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_mistral4 as km
from perfbench import program_spans

SPANS = {"decode": ("ds.serve.decode.dispatch", "active"), "chunk": ("ds.serve.chunk", "tokens")}


def read(ctx, pattern, kind):
    tr = ctx.trace
    if tr is None or ctx.traced is None:
        return None
    kernel_s = tr.seconds_matching(pattern)
    span, per_query = SPANS[kind]
    recs = program_spans.records_in(ctx.traced)
    calls = [r[3] for r in recs or () if r[0] == span and "attended" in r[3] and per_query in r[3]]
    if kernel_s <= 0 or not calls:
        return None
    c = ctx.config
    L = int(c["num_hidden_layers"])
    row, val = km.widths(c)
    itemsize = 2 if c["dtype"] == "bfloat16" else 4
    pairs = L * sum(int(a["attended"]) for a in calls)
    rows = pairs if kind == "decode" else pairs / int(c["serving"]["prefill_chunk_tokens"])
    f, b = km.latent_attention(
        pairs, rows, L * sum(int(a[per_query]) for a in calls), int(c["num_attention_heads"]), row, val, itemsize
    )
    return 100.0 * kc.min_seconds(f, b, ctx.peak)[0] / kernel_s
