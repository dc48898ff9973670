"""The sum over the window of attribute ``attr`` of the program's spans called
``name`` (a list of names: each is read), as a share in percent of the sum of
attribute ``over`` of the same spans. Only spans that carry both count: a
program without the attributes gives nothing. The counts are the program's
own, made where the work happens."""

from perfbench import program_spans


def read(ctx, name, attr, over):
    names = {name} if isinstance(name, str) else set(name)
    recs = program_spans.records_in(ctx.window)
    both = [r[3] for r in recs or () if r[0] in names and attr in r[3] and over in r[3]]
    base = sum(float(a[over]) for a in both)
    return 100.0 * sum(float(a[attr]) for a in both) / base if base > 0 else None
