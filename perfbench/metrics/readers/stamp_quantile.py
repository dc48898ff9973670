"""A quantile over the requests counted in the window of one reading per
request, from the host-clock stamps: ``lateness`` (submitted - due),
``queue_wait`` (admitted - due), ``ttft`` (first token - due),
``latency_per_token`` ((last emission - due) / tokens generated)."""

from perfbench import arith

FIELDS = {
    "lateness": lambda r: r.t_submit - r.due,
    "queue_wait": lambda r: None if r.t_admit is None else r.t_admit - r.due,
    "ttft": lambda r: None if r.t_first_token is None else r.t_first_token - r.due,
    "latency_per_token": arith.latency_per_token,
}


def read(ctx, field, q):
    vals = [FIELDS[field](r) for r in ctx.recs if r.counted]
    return arith.quantile([v for v in vals if v is not None], float(q))
