"""How uneven the held experts' loads are: over the window's decode steps, the
mean of (the most tokens one held expert got in one layer) over (the mean
tokens a held expert got a layer), from ``ds.serve.emit``'s ``moe_load_max``
and ``moe_pairs_held``. 1 is an even split; the products run over the fullest
expert's rows. A program without the attributes gives nothing."""

from perfbench import kernel_costs_exaone_moe as kx
from perfbench import program_spans


def read(ctx):
    recs = program_spans.records_in(ctx.window)
    c = ctx.config
    per_step = int(c["num_experts"]) * kx.sparse_layers(c)
    vals = [r[3]["moe_load_max"] * per_step / r[3]["moe_pairs_held"] for r in recs or ()
            if r[0] == "ds.serve.emit" and r[3].get("moe_pairs_held")]
    return sum(vals) / len(vals) if vals else None
