"""A quantile of the device time of the programs of one ``kind`` that the
serving loop launched in the traced window (``plain``: the decode program;
``mixed``: the chunk program with decode rows; ``chunk``: with none;
``prefill``; ``verify``). The kind is the launch's own, carried by its leaf
span and joined to the device program by the call's ids
(perfbench/launches.py): one compiled program that runs as two kinds of step
reads as two. Nothing under 8 programs, and nothing from a program whose
leaves carry no launch number."""

from perfbench import launches


def read(ctx, kind, q):
    rows = launches.rows(ctx)
    if not rows:
        return None
    return launches.quantile(launches.device_seconds(rows, kind), q)
