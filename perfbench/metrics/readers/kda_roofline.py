"""A KDA kernel's share of its roofline over the traced part of the window
(the delta rule with a decay a key channel, ``kda_step`` / ``kda_chunk``):
``gdn_roofline`` for a configuration whose keys are the ``bailing_hybrid``
ones. The least time the chip could take for what the kernel's calls need
(perfbench/kernel_costs_ling3.py: a call on a slot reads and writes that
slot's matrix state once, a row its inputs, its ``H x dk`` decays among them,
and its output; the larger of the bytes' time and the operations') over the
device time of the operations matching ``pattern``. ``kernel``: ``"step"`` (a
decode step's live rows, or the decode rows a mixed call carries) or
``"chunk"`` (the chunk calls' real rows).

The rows are the program's own counts: ``ds.serve.decode.dispatch``'s
``active`` (a row and a call on a slot each; a chunk that rode a decode step
is inside one), ``ds.serve.chunk``'s ``rows_self`` (the chunk calls' real rows)
and its ``chunks`` + ``rode`` (the calls), every "lin" sub-block. A program
without the spans, or whose configuration has no such sub-block, gives
nothing."""

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_ling3 as kl
from perfbench import program_spans


def read(ctx, pattern, kernel):
    tr = ctx.trace
    if tr is None or ctx.traced is None or "kda_lower_bound" not in ctx.config:
        return None
    kernel_s = tr.seconds_matching(pattern)
    recs = program_spans.records_in(ctx.traced) or ()
    layers = kl.kinds(ctx.config).count("lin")
    if kernel == "step":
        rows = sum(int(r[3]["active"]) for r in recs if r[0] == "ds.serve.decode.dispatch" and "active" in r[3])
        f, b = kl.kda_step(rows, ctx.config)
    else:
        chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk" and "rows_self" in r[3]]
        rows = sum(int(c["rows_self"]) for c in chunks)
        f, b = kl.kda_chunk(rows, sum(int(c.get("chunks", 0)) + int(c.get("rode", 0)) for c in chunks), ctx.config)
    if kernel_s <= 0 or rows <= 0:
        return None
    return 100.0 * kc.min_seconds(layers * f, layers * b, ctx.peak)[0] / kernel_s
