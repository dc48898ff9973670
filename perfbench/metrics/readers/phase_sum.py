"""Seconds of set-up the program spent in the phases called one of ``names``
(``deepspeed_tpu.telemetry.spans.phases``) before the window opened, less the
phases called one of ``minus_nested`` that lie inside them, so that the parts
of ``setup_s`` add up. 0.0, not nothing, where the process had none (programs
already compiled); nothing where the program has no phases at all."""

from perfbench import program_spans


def read(ctx, names, minus_nested=()):
    program_spans.log_phases(ctx)
    return program_spans.phase_seconds(tuple(names), ctx.window[0], tuple(minus_nested))
