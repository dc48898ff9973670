"""Self time of the device operations that belong to some parts of the model
(the ``dspart.*`` scopes of ``deepspeed_tpu.telemetry.parts``), as a share in
percent of the device's busy time over the traced part of the window.
perfbench/program_parts.py joins the run's own trace to the program's part
tables by (module, instruction).

``parts``: a list of parts or prefixes of parts (``"attn"`` takes ``attn.qkv``,
``""`` every part), or ``"none"``: what no part was given to. ``phase``: only
the forward (``fwd``), the backward (``bwd``), the forward run again under
remat (``recompute``) or what belongs to no pass (``none``). ``dot``: only the
instructions that hold a matmul (true) or hold none (false)."""

from perfbench import program_parts


def read(ctx, parts, of, phase=None, dot=None):
    seconds = program_parts.by_part(ctx)
    if seconds is None or of != "busy":
        return None
    return program_parts.share(seconds, ctx.trace.busy_s, parts, phase, dot)
