"""A quantile of how long what a program sampled lay on the chip: from the
program's end on the device to the start of the ``ds.serve.emit`` that hands
it out, over the step programs of the traced window (``of`` ``token``) or
over the programs that sampled a request's FIRST token (``of`` ``first``: the
emit that names the program in ``firsts``, or the end of the synchronous wait
that names it). The device's end is moved onto the host's clock by an offset
estimated from below, so a hold reads too long by at most the shortest launch
latency of the trace (perfbench/launches.py). Nothing under ``least``
programs (8, unless the entry states what its cell's traced window holds), and
nothing from a program whose leaves carry no launch number."""

from perfbench import launches


def read(ctx, of, q, least=launches.MIN_PROGRAMS):
    rows = launches.rows(ctx)
    if not rows:
        return None
    return launches.quantile(launches.holds(rows, of), q, least)
