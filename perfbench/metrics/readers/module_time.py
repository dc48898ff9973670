"""A quantile of the device time of one execution of the programs whose name
matches ``pattern`` (the trace's line of executed programs)."""

import re

from perfbench import arith


def read(ctx, pattern, q):
    if ctx.trace is None:
        return None
    rx = re.compile(pattern)
    vals = [d for name, ds in ctx.trace.module_durations.items() if rx.search(name) for d in ds]
    return arith.quantile(vals, float(q))
