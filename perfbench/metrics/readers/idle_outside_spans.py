"""Of the device's idle time in the traced window, the share in percent that
falls under no leaf span of the program (``ds.*``; under a step's parent span
alone, under the runner's own spans, or under none): what the program's
instrumentation cannot put a name to. perfbench/program_spans.py splits each idle gap over the leaves by
intersection, on one clock."""

from perfbench import program_spans


def read(ctx):
    idle = program_spans.idle_by_leaf(ctx)
    if not idle or sum(idle.values()) <= 0:
        return None
    unnamed = sum(v for k, v in idle.items() if not k.startswith("ds."))
    return 100.0 * unnamed / sum(idle.values())
