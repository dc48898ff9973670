"""``moe_weight_stream_roofline`` for a configuration whose keys are the
``mistral4`` ones: the routed experts' share of their roofline over the traced
part of the window, the least time the chip could take to stream the weights
of the held experts that were hit and the tokens' activations (and to do the
pairs' products), over the device time of the operations matching ``pattern``.

What was hit is the program's count, as in the other expert family's reader: a
decode step's ``ds.serve.emit`` carries ``moe_experts_hit`` and
``moe_pairs_held``; chunk calls report with their prompt's last one
(``ds.serve.chunk``: ``moe_calls`` calls in all), so the chunk calls made in
the traced part (``chunks``) are charged the window's mean hit and pairs a
call, never more than every held expert. A program without the attributes
gives nothing. Costs: perfbench/kernel_costs_mistral4.py."""

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_mistral4 as km
from perfbench import program_spans


def read(ctx, pattern):
    tr = ctx.trace
    if tr is None or ctx.traced is None:
        return None
    ops_s = tr.seconds_matching(pattern)
    traced = program_spans.records_in(ctx.traced)
    emits = [r[3] for r in traced or () if r[0] == "ds.serve.emit" and "moe_experts_hit" in r[3]]
    if ops_s <= 0 or not emits:
        return None
    c = ctx.config
    n_sparse = km.sparse_layers(c)
    slots = int(c["serving"]["max_slots"])
    hit = sum(int(a["moe_experts_hit"]) for a in emits)
    pairs = sum(int(a["moe_pairs_held"]) for a in emits)
    tokens = len(emits) * slots * n_sparse          # every slot's row goes through, idle or not
    whole = [r[3] for r in program_spans.records_in(ctx.window) or () if r[0] == "ds.serve.chunk" and r[3].get("moe_calls")]
    calls = sum(int(r[3].get("chunks", 0)) for r in traced if r[0] == "ds.serve.chunk")
    if whole and calls:
        n = sum(int(a["moe_calls"]) for a in whole)
        hit += calls * min(sum(int(a["moe_experts_hit"]) for a in whole) / n, int(c["n_routed_experts"]) * n_sparse)
        pairs += calls * sum(int(a["moe_pairs_held"]) for a in whole) / n
        tokens += calls * int(c["serving"]["prefill_chunk_tokens"]) * n_sparse
    itemsize = 2 if c["dtype"] == "bfloat16" else 4
    f, b = km.routed_experts(hit, pairs, tokens, int(c["hidden_size"]), int(c["moe_intermediate_size"]), itemsize)
    return 100.0 * kc.min_seconds(f, b, ctx.peak)[0] / ops_s
