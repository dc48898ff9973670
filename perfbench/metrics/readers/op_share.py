"""Self time of the device operations of one category (or matching one
pattern), as a share in percent of the device's busy time (``of: busy``) or of
the traced window (``of: window``). On the compute line an operation's self
time is time in which nothing else ran there: a collective's share of the
window is the part of it that no compute hid."""


def read(ctx, of, category=None, pattern=None):
    tr = ctx.trace
    if tr is None:
        return None
    secs = tr.seconds_in_category(category) if category else tr.seconds_matching(pattern)
    base = tr.busy_s if of == "busy" else tr.window_s
    return 100.0 * secs / base if base > 0 else None
