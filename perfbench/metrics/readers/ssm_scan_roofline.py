"""The selective-scan kernels' share of their roofline over the traced part of
the window: the least time the HBM could take for the bytes the scans' rows
need (perfbench/kernel_costs_phi4flash.py; the kernels multiply no matrix, so
the bound is bytes alone) over the device time of the operations matching
``pattern``.

The rows are the program's own counts: ``ds.serve.decode.dispatch``'s
``active`` (a row and a call on a slot each), ``ds.serve.chunk``'s
``rows_self`` (the chunk calls' real rows) and its ``chunks`` + ``rode`` (the
calls), every "ssm" sub-block. A program without the spans, or whose chunk
span has no ``rows_self`` (no recurrent family), gives nothing."""

from perfbench import kernel_costs_phi4flash as kp
from perfbench import program_spans


def read(ctx, pattern):
    tr = ctx.trace
    if tr is None or ctx.traced is None:
        return None
    kernel_s = tr.seconds_matching(pattern)
    recs = program_spans.records_in(ctx.traced) or ()
    steps = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch" and "active" in r[3]]
    chunks = [r[3] for r in recs if r[0] == "ds.serve.chunk" and "rows_self" in r[3]]
    if kernel_s <= 0 or not (steps or chunks):
        return None
    d_inner, d_state = kp.sizes(ctx.config)
    layers = kp.kinds(ctx.config).count("ssm")
    active = sum(int(s["active"]) for s in steps)
    calls = sum(int(c.get("chunks", 0)) + int(c.get("rode", 0)) for c in chunks)
    nbytes = layers * kp.selective_scan(active + sum(int(c["rows_self"]) for c in chunks), active + calls, d_inner, d_state)
    return 100.0 * (nbytes / ctx.peak.hbm_bytes_per_s) / kernel_s
