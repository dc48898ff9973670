"""``moe_load_max_over_mean`` for a configuration whose keys are the
``mistral4`` ones (``n_routed_experts`` held, every layer after
``first_k_dense_replace`` an expert layer): over the window's decode steps, the
mean of (the most tokens one held expert got in one layer) over (the mean
tokens a held expert got a layer), from ``ds.serve.emit``'s ``moe_load_max``
and ``moe_pairs_held``. A program without the attributes gives nothing."""

from perfbench import kernel_costs_mistral4 as km
from perfbench import program_spans


def read(ctx):
    recs = program_spans.records_in(ctx.window)
    c = ctx.config
    per_step = int(c["n_routed_experts"]) * km.sparse_layers(c)
    vals = [r[3]["moe_load_max"] * per_step / r[3]["moe_pairs_held"] for r in recs or ()
            if r[0] == "ds.serve.emit" and r[3].get("moe_pairs_held")]
    return sum(vals) / len(vals) if vals else None
