"""The mean over the window of attribute ``attr`` of the program's spans
called ``name``, as a share in percent of the configuration value at ``over``
(a dotted key: ``serving.max_slots``). The count is the program's own, made
where the work happens."""

from perfbench import program_spans


def read(ctx, name, attr, over):
    recs = program_spans.records_in(ctx.window)
    vals = [r[3][attr] for r in recs or () if r[0] == name and attr in r[3]]
    if not vals:
        return None
    base = ctx.config
    for key in over.split("."):
        base = base[key]
    return 100.0 * sum(vals) / (len(vals) * float(base))
