"""The one traffic generator. A mix is a data file of parameters
(``traffic/<name>.json``); this module turns it into arrivals.

**The offered load does not depend on the seed.** Time is cut into blocks of
``block_s`` seconds. Every block holds exactly ``round(rate_rps * block_s)``
requests (or ``block_requests`` for a backlog), and their
(prompt length, new tokens) pairs are the quantile points of the stated
distributions: the same multiset in every block. The arrival offsets of a block
are that many draws from the block's arrival profile, sorted (a Poisson
process given its count), and the order in which the pairs arrive is a
permutation; both are drawn from the mix's own ``arrival_seed`` and
``order_seed`` and the block's number, so they are irregular, differ from block
to block, and are the same for every ``--seed``. ``--seed`` decides the token
ids (and, in the runners, the weights and the training batches). So two runs
offer the same requests at the same times, with other contents.

Why so strict: measured on the chip in PR 23 (PERF.md, Findings). With offsets
and order drawn from ``--seed`` the slots' occupancy moved with the seed and
every latency with it (4% between seeds where one seed repeated to 0.5%); with
offsets fixed and only the order seeded, the pooled gaps still moved 3%,
because the decode kernel's time depends on which contexts share the slots.
A mix that wants another schedule states another ``arrival_seed`` or
``order_seed``: that is a new file, and a new cell.

Parameters of a mix::

    {"loop": "open",            # open | backlog | train_steps
     "rate_rps": 1.0,           # open: requests per second
     "block_s": 10,
     "block_requests": 32,      # backlog: size of the multiset that is cycled
     "components": [            # shares sum to 1; one is the common case
        {"share": 1.0,
         "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.9, "min": 32, "max": 512},
         "new_tokens": {"dist": "const", "value": 64},
         "shared_prefix": {"tokens": 0, "groups": 1}}],
     "profile": [[0, 10, 1.0]], # open: relative arrival intensity inside a block
     "arrival_seed": 0,         # open: the arrival offsets come from this, not from --seed
     "order_seed": 0,           # which pair arrives when comes from this, not from --seed
     "ramp_s": 8,               # open: load offered before the window opens (set-up)
     "ramp": {"requests": 160, "aged": true}}  # backlog: the window opens once that many requests of
                                # the cycle are submitted (or after "seconds" of the clock, for a mix
                                # that states no count); see runners/serve.py

Distributions: ``const`` (value), ``uniform`` (min, max), ``lognormal``
(median, sigma, clipped to min..max). Quantile point i of n is the
distribution's value at (i + 0.5) / n.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Tuple

import numpy as np

_SEED_MOD = 2**63


@dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds from the window's opening (negative: ramp)
    prompt_len: int
    new_tokens: int
    block: int
    index: int            # position in the block's multiset (seed-independent identity)
    component: int
    prefix_group: int     # -1: no shared prefix


def quantile_points(dist: dict, n: int) -> List[int]:
    """The n quantile points of ``dist`` at (i + 0.5) / n, as whole numbers."""
    kind = dist["dist"]
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "const":
        vals = [float(dist["value"])] * n
    elif kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        vals = [lo + q * (hi - lo) for q in qs]
    elif kind == "lognormal":
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        nd = NormalDist()
        vals = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo = dist.get("min", -math.inf)
    hi = dist.get("max", math.inf)
    return [int(round(min(max(v, lo), hi))) for v in vals]


def block_count(traffic: dict) -> int:
    if traffic["loop"] == "open":
        return int(round(float(traffic["rate_rps"]) * float(traffic["block_s"])))
    return int(traffic["block_requests"])


def block_multiset(traffic: dict) -> List[Tuple[int, int, int, int]]:
    """(prompt_len, new_tokens, component, prefix_group) for one block: the same
    list for every block and every seed."""
    n = block_count(traffic)
    comps = traffic["components"]
    shares = [float(c.get("share", 1.0)) for c in comps]
    counts = [int(math.floor(s / sum(shares) * n)) for s in shares]
    # hand the remainder to the largest fractional parts, first component first
    rest = n - sum(counts)
    order = sorted(range(len(comps)), key=lambda i: -((shares[i] / sum(shares) * n) % 1.0))
    for i in order[:rest]:
        counts[i] += 1
    out = []
    for ci, (c, k) in enumerate(zip(comps, counts)):
        if k == 0:
            continue
        plens = quantile_points(c["prompt_len"], k)
        news = quantile_points(c["new_tokens"], k)
        # pair long prompts with every length of answer: a fixed stride walk
        # that is the same for every seed (a coprime stride visits each once)
        stride = next(s for s in range(max(1, int(k * 0.618)), 2 * k + 2) if math.gcd(s, k) == 1)
        groups = int(c.get("shared_prefix", {}).get("groups", 0) or 0)
        ptoks = int(c.get("shared_prefix", {}).get("tokens", 0) or 0)
        for i in range(k):
            out.append((plens[i], news[(i * stride) % k], ci, (i % groups) if (groups and ptoks) else -1))
    return out


def _profile_inverse(profile, block_s: float):
    """u in [0,1) -> offset in the block, for a piecewise-constant intensity."""
    segs = [(float(a), float(b), float(w)) for a, b, w in (profile or [[0.0, block_s, 1.0]])]
    mass = [(b - a) * w for a, b, w in segs]
    total = sum(mass)
    edges = np.cumsum([0.0] + mass) / total

    def inv(u):
        k = np.clip(np.searchsorted(edges, u, side="right") - 1, 0, len(segs) - 1)
        a = np.array([s[0] for s in segs])[k]
        b = np.array([s[1] for s in segs])[k]
        frac = (u - edges[k]) / np.maximum(edges[k + 1] - edges[k], 1e-300)
        return a + frac * (b - a)

    return inv


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % _SEED_MOD, *[int(s) % _SEED_MOD for s in stream]])


def open_block(traffic: dict, block: int) -> List[Arrival]:
    """Arrivals of one block of an open loop, sorted by due time. No seed comes
    in: the schedule is the mix's own."""
    bs = float(traffic["block_s"])
    pairs = block_multiset(traffic)
    order = _rng(int(traffic.get("order_seed", 0)), 1, block + 2**20).permutation(len(pairs))
    u = _rng(int(traffic.get("arrival_seed", 0)), 6, block + 2**20).random(len(pairs))
    offs = np.sort(_profile_inverse(traffic.get("profile"), bs)(u))
    return [
        Arrival(block * bs + float(offs[j]), pairs[i][0], pairs[i][1], block, int(i), pairs[i][2], pairs[i][3])
        for j, i in enumerate(order)
    ]


def open_arrivals(traffic: dict, t_from: float, t_to: float) -> List[Arrival]:
    """Arrivals due in [t_from, t_to), block by block. The window opens at 0;
    t_from < 0 is the ramp."""
    bs = float(traffic["block_s"])
    out = []
    for b in range(int(math.floor(t_from / bs)), int(math.ceil(t_to / bs))):
        out.extend(a for a in open_block(traffic, b) if t_from <= a.due_s < t_to)
    return out


def backlog_cycle(traffic: dict) -> Iterator[Arrival]:
    """An endless backlog: the block's multiset in the mix's own order
    (``order_seed``), again and again, a new order each cycle. ``due_s`` is 0: a
    backlog has no arrivals. The arrivals carry their cycle and index, from
    which :func:`prompt_tokens` draws the ids that ``--seed`` decides."""
    pairs = block_multiset(traffic)
    cycle = 0
    while True:
        for i in _rng(int(traffic.get("order_seed", 0)), 2, cycle).permutation(len(pairs)):
            yield Arrival(0.0, pairs[i][0], pairs[i][1], cycle, int(i), pairs[i][2], pairs[i][3])
        cycle += 1


def prompt_tokens(traffic: dict, seed: int, a: Arrival, vocab: int) -> np.ndarray:
    """Token ids of one arrival's prompt, from the seed. Arrivals of one
    prefix group share their first ``shared_prefix.tokens`` ids."""
    ids = _rng(seed, 3, a.block + 2**20, a.index).integers(0, vocab, a.prompt_len, dtype=np.int64)
    if a.prefix_group >= 0:
        n = min(int(traffic["components"][a.component]["shared_prefix"]["tokens"]), a.prompt_len)
        ids[:n] = _rng(seed, 4, a.component, a.prefix_group).integers(0, vocab, n, dtype=np.int64)
    return ids.astype(np.int32)


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int) -> np.ndarray:
    """A fresh seeded batch of token ids for training step ``step``."""
    return _rng(seed, 5, step + 2**20).integers(0, vocab, (batch, seq), dtype=np.int64).astype(np.int32)


def offered(traffic: dict) -> dict:
    """What one block offers, for PERF.md and the tests: requests and tokens."""
    ms = block_multiset(traffic)
    return {
        "requests": len(ms),
        "prompt_tokens": sum(p for p, _, _, _ in ms),
        "new_tokens": sum(n for _, n, _, _ in ms),
    }
