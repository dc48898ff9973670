"""Operations and bytes of the kernels the ``mistral4`` configuration brings a
roofline share for, computed from shapes and from what the program counted, in
``kernel_costs.py``'s sense: the algorithm's needs, never more.

- latent (MLA) attention, absorbed: a (query token, cached row) pair costs,
  for each of the ``n_head`` query heads, a score over the row's
  ``row_width`` values and a value product over its first ``v_width``: two
  products of 2 FLOP a multiply-add. The row is ``row_width`` values as the
  family defines it (320), NOT the lane-padded row the pool stores (384): the
  padding is the implementation's. The decode step reads each attended row
  once for its one query; a chunk call's pairs are the causal triangle and a
  row serves at most ``chunk`` queries, so it reads at least ``pairs / chunk``
  rows. Queries are read and outputs written once.
- the routed experts of an expert-layer call: ``kernel_costs_exaone_moe.
  routed_experts`` as it is (the weights of the held experts that were hit,
  the tokens' activations, the pairs' products).
"""

from __future__ import annotations

from perfbench.kernel_costs_exaone_moe import routed_experts  # noqa: F401  (the readers take it from here)


def widths(cfg: dict):
    """(cached row, values inside it) of a ``mistral4`` configuration."""
    return int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]), int(cfg["kv_lora_rank"])


def latent_attention(pairs: int, rows: float, queries: int, n_head: int, row_width: int, v_width: int, itemsize: int):
    """``pairs`` (query token, cached row) pairs attended, ``rows`` cached rows
    read, ``queries`` query tokens, each summed over calls AND layers."""
    flops = 2 * (row_width + v_width) * n_head * pairs
    nbytes = rows * row_width * itemsize + queries * n_head * (row_width + v_width) * itemsize
    return flops, nbytes


def sparse_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg.get("first_k_dense_replace", 0))
