"""A quantile over the window's steps of the host-clock duration of the
program's spans called ``name`` (``deepspeed_tpu.telemetry.spans``): with
``minus_suffix`` each span's duration less the spans nested in it whose name
ends so (``.wait``: where the host blocks on the device), with ``min_attr``
only the spans whose attributes reach the values given."""

from perfbench import arith, program_spans


def read(ctx, name, q, minus_suffix=None, min_attr=None):
    recs = program_spans.records_in(ctx.window)
    if recs is None:
        return None
    return arith.quantile(program_spans.own_durations(recs, name, minus_suffix, min_attr), float(q))
