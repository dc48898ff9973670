"""Operations and bytes a kernel call needs, computed from its shapes. These
are the algorithm's needs, not what an implementation happens to move: a
roofline share above 100% would mean one of these counts too much.

``min_seconds`` is the roofline bound: the larger of operations over the peak
FLOP/s and bytes over the peak HBM bandwidth; it also says which of the two
bounds.
"""

from __future__ import annotations

from .peaks import Peak


def paged_decode(attended_tokens: int, n_head: int, head_dim: int, itemsize: int, n_slots: int):
    """One decode-attention call over one layer: every active slot's query
    attends its own context. ``attended_tokens`` is the sum of the slots'
    context lengths. Reads each attended K and V row once, the queries, and
    writes the outputs; q.k and p.v are 2 FLOP per multiply-add."""
    flops = 2 * 2 * attended_tokens * n_head * head_dim
    nbytes = 2 * attended_tokens * n_head * head_dim * itemsize + 2 * n_slots * n_head * head_dim * itemsize
    return flops, nbytes


def flash_causal(batch: int, seq: int, n_head: int, head_dim: int, itemsize: int, backward: bool):
    """Causal self-attention over [batch, seq, n_head, head_dim]. Forward: two
    matrix products over the lower triangle (q.k^T, p.v). Backward: five
    (s, dp, dv, dq, dk), recomputing s; q, k, v, o (and their gradients) are
    each read or written once."""
    tri = seq * (seq + 1) // 2
    per_product = 2 * batch * n_head * tri * head_dim
    flops = (5 if backward else 2) * per_product
    tensors = 8 if backward else 4  # q k v o (+ do dq dk dv)
    nbytes = tensors * batch * seq * n_head * head_dim * itemsize
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, peak: Peak):
    t_f, t_b = flops / peak.flops_bf16, nbytes / peak.hbm_bytes_per_s
    return max(t_f, t_b), ("compute" if t_f >= t_b else "memory")
