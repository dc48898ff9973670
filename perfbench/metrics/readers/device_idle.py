"""1 - (union of the device's operation intervals) / traced window, percent,
averaged over the chips used."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
