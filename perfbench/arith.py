"""Metric arithmetic over host-clock stamps. Pure functions of numbers: the
tier-1 tests drive them with hand-made stamps (tests/perfbench/test_arith.py).

A served request is a :class:`Rec`: the due time the generator kept, and the
stamps the program put on the request where the token was emitted
(``t_submit``, ``t_admit``, ``t_first_token``, ``t_emissions``), all on the
benchmark's clock (the server is given ``time.perf_counter``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence


@dataclass
class Rec:
    due: float
    prompt_len: int
    new_tokens: int
    t_submit: float
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    t_emissions: List[float] = field(default_factory=list)
    status: str = ""
    n_tokens: int = 0
    counted: bool = False    # due (or, for a backlog, finished) inside the window


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated quantile (numpy's default), None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def tokens_in_window(recs: Sequence[Rec], t0: float, t1: float) -> float:
    """Generated tokens whose emission stamp lies in [t0, t1), plus prompt
    tokens prefilled in it: a prompt whose prefill (``t_admit`` to
    ``t_first_token``) straddles an edge counts in proportion. Whole requests
    are never counted; a request that started before the window or ended after
    it gives what fell inside."""
    total = 0.0
    for r in recs:
        total += sum(1 for t in r.t_emissions if t0 <= t < t1)
        if r.t_admit is None:
            continue
        # an unfinished prefill has no first-token stamp: nothing of it is
        # known to be done, so nothing is counted
        if r.t_first_token is None:
            continue
        span = r.t_first_token - r.t_admit
        if span <= 0:
            total += r.prompt_len if t0 <= r.t_first_token < t1 else 0
        else:
            total += r.prompt_len * overlap(r.t_admit, r.t_first_token, t0, t1) / span
    return total


def pooled_gaps(recs: Sequence[Rec], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive emissions of every request, pooled; a gap
    belongs to the window if the token that ends it was emitted in [t0, t1)."""
    gaps = []
    for r in recs:
        e = r.t_emissions
        gaps.extend(e[i] - e[i - 1] for i in range(1, len(e)) if t0 <= e[i] < t1)
    return gaps


def latency_per_token(r: Rec) -> Optional[float]:
    """(last emission - due time) / tokens generated: what a user waited per
    token, queue wait and time to first token folded in."""
    if not r.t_emissions:
        return None
    return (r.t_emissions[-1] - r.due) / len(r.t_emissions)


def train_rate(step_ends: Sequence[float], tokens_per_step: int, t0: float, t1: float):
    """Steps back to back: boundary i is the time step i's loss was ready.
    Whole steps that start and end inside [t0, t1]: tokens over the time
    between the first and the last boundary inside. Returns
    (tokens_per_s, n_steps) or (None, 0)."""
    inside = [t for t in step_ends if t0 <= t <= t1]
    if len(inside) < 2:
        return None, 0
    n = len(inside) - 1
    return n * tokens_per_step / (inside[-1] - inside[0]), n
