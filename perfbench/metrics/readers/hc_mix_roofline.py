"""The multi-stream residual's mixing kernels' share of their roofline over the
traced part of the window: the least time the chip could take for what the
kernels' calls need (perfbench/kernel_costs_xing4.py: ``phi`` once a call a sub-block from
HBM, the rows' passes stay on the chip, and a real row's operations; the larger
of the bytes' time and the operations') over the device time of the operations matching ``pattern``.

The rows are the program's own: every launch of a serving program in the
traced window says what it carried (``rows`` decode rows and ``tokens`` prompt
tokens on its leaf span, perfbench/launches.py), each through ``2 x
num_hidden_layers`` sub-blocks; a row's bytes are the program's gauge
(``hc_row_bytes`` as ``ds.init.programs`` records it). A program without the
gauge, without numbered launches or without the kernels gives nothing."""

from perfbench import kernel_costs as kc
from perfbench import kernel_costs_xing4 as kx
from perfbench import launches, program_spans


def read(ctx, pattern):
    tr = ctx.trace
    mod = program_spans.program()
    if tr is None or mod is None or not hasattr(mod, "phases") or "hc_mult" not in ctx.config:
        return None
    row_bytes = [p[3]["hc_row_bytes"] for p in mod.phases() if p[0] == "ds.init.programs" and "hc_row_bytes" in p[3]]
    kernel_s = tr.seconds_matching(pattern)
    rows = launches.rows(ctx)
    if not row_bytes or kernel_s <= 0 or not rows:
        return None
    c = ctx.config
    n, E = int(c["hc_mult"]), int(c["hidden_size"])
    carried = sum(r.rows + r.tokens for r in rows)
    f, b = kx.hc_mix(carried, len(rows), 2 * int(c["num_hidden_layers"]), n, E, int(row_bytes[-1]) // (n * E))
    return 100.0 * kc.min_seconds(f, b, ctx.peak)[0] / kernel_s
