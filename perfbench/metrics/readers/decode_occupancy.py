"""Active decode slots over ``max_slots``, averaged over the window's decode
steps (a step that emitted no decode token is not a decode step), in percent."""


def read(ctx):
    steps = [s for s in ctx.steps_in(ctx.window, "srv.step") if s.info.get("decode_tokens", 0) > 0]
    if not steps:
        return None
    slots = int(ctx.config["serving"]["max_slots"])
    return 100.0 * sum(s.info["decode_tokens"] for s in steps) / (len(steps) * slots)
