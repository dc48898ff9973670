"""Operations and bytes of the kernels the ``xing4_0`` configuration brings a
roofline share for, computed from shapes and from what the program counted, in
``kernel_costs.py``'s sense: the algorithm's needs, never more.

The mixing of an ``n``-stream residual around a sub-block
(``ops/pallas/hyper_connection.py``) passes over a token's row of ``n E``
values three times (the maps and the pre-mixed row; the read and the write of
the write back). None of those passes has to cross the chip's edge: a call's
rows, 1.8 MB at 64 and 9.2 MB at 320, fit the chip's fast memory, and the
compiled programs keep them there from ``embed`` to ``logits`` (the operands
of both kernels carry ``S(1)`` in the compiled text; PERF.md, PR 57: counted
as three passes through HBM, as the issue had it, the kernels read 126% of
that roofline). What the algorithm needs from HBM is each sub-block's ``phi``,
once a call. Its operations: the projection ``2 n E (2n + n^2)``, the
statistic ``2 n E``, the pre-mix ``2 n E`` and the write back ``2 n^2 E + 2 n
E`` a row a sub-block. The Sinkhorn rounds are left out: they are the
algorithm's latency (``n^2`` values a row), not its work. Most of those
operations run on the vector unit, whose peak is below the matrix unit's that
the roofline takes: the share reads low, and says how far the kernels are from
what no implementation on this chip could beat, not from what this one could.

The latent attention and the routed experts: ``kernel_costs_mistral4``'s as
they are (this configuration's keys are the ones it reads).
"""

from __future__ import annotations


def hc_mix(rows: int, calls: int, sub_blocks: int, n: int, E: int, itemsize: int):
    """``rows`` real rows summed over ``calls`` calls of a program of
    ``sub_blocks`` sub-blocks, streams ``E`` wide in ``itemsize`` bytes →
    (FLOPs, bytes)."""
    K = 2 * n + n * n
    flops = rows * sub_blocks * (2 * n * E * K + 2 * n * E + 2 * n * E + 2 * n * n * E + 2 * n * E)
    nbytes = sub_blocks * itemsize * calls * K * n * E
    return flops, nbytes
