"""``lin_state_bytes_share`` for a configuration whose keys are the
``bailing_hybrid`` ones (Ling-3.0-flash): the matrix states' share, in percent,
of the bytes a decode step must move, over the window's decode steps. The
state's bytes a live slot are the program's own gauge (``serving_lin_state_bytes``
as ``ds.init.programs`` records it), read and written once a step; beside them
what the step's other parts must move, from the program's counts
(``ds.serve.decode.dispatch``'s ``active`` and ``attended``, ``ds.serve.emit``'s
``moe_experts_hit``) and the configuration's shapes
(perfbench/kernel_costs_ling3.decode_step_bytes: the latent rows of the latent
layers alone, the dense layers' FFNs among the shared weights). A program
without the gauge or the spans gives nothing."""

from perfbench import kernel_costs_ling3 as kl
from perfbench import program_spans


def read(ctx):
    mod = program_spans.program()
    recs = program_spans.records_in(ctx.window)
    if mod is None or not recs or not hasattr(mod, "phases"):
        return None
    pools = [p[3]["lin_state_bytes"] for p in mod.phases() if p[0] == "ds.init.programs" and "lin_state_bytes" in p[3]]
    steps = [r[3] for r in recs if r[0] == "ds.serve.decode.dispatch" and "attended" in r[3]]
    emits = [r[3] for r in recs if r[0] == "ds.serve.emit" and "moe_experts_hit" in r[3]]
    if not pools or not steps or not emits:
        return None
    c = ctx.config
    a_slot = int(pools[-1]) / int(c["serving"]["max_slots"])          # every "lin" sub-block's state of one slot
    active = sum(int(s["active"]) for s in steps)
    parts = kl.decode_step_bytes(c, 0, sum(int(s["attended"]) for s in steps),
                                 sum(int(e["moe_experts_hit"]) for e in emits) * len(steps) / len(emits))
    state = 2 * a_slot * active
    rest = parts["experts"] + parts["keys"] + parts["shared_weights"] * len(steps)
    return 100.0 * state / (state + rest)
