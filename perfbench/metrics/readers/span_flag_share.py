"""The share in percent of the program's spans called ``name`` in the window
whose attribute ``attr`` is set (not 0), of those that carry it. The flag is the
program's own, set where the work happens. A program whose spans do not carry
the attribute gives nothing."""

from perfbench import program_spans


def read(ctx, name, attr):
    recs = program_spans.records_in(ctx.window)
    flags = [bool(r[3][attr]) for r in recs or () if r[0] == name and attr in r[3]]
    return 100.0 * sum(flags) / len(flags) if flags else None
