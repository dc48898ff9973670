"""Operations and bytes of the two kernels the ``exaone_moe`` configuration
brings a roofline share for, computed from shapes and from what the program
counted, in ``kernel_costs.py``'s sense: the algorithm's needs, never more.

- the paged decode kernel over layers of two kinds: a full-attention layer
  reads each slot's whole context, a sliding-window layer at most ``window``
  keys of it; ``n_kv_head`` K and V heads of ``head_dim`` are read, whatever
  the number of query heads;
- the routed experts of one expert layer call: the three matrices of every
  held expert that some token of the call was routed to (``experts_hit``),
  the tokens' activations in and out once, and two products of 2 FLOP a
  multiply-add for gate and up and one for down, for the pairs held.
"""

from __future__ import annotations


def sparse_layers(cfg: dict) -> int:
    return sum(1 for t in cfg["mlp_layer_types"][: int(cfg["num_hidden_layers"])] if t == "sparse")


def paged_decode_keys(keys_read: int, n_kv_head: int, n_head: int, head_dim: int, itemsize: int, n_queries: int):
    """Decode attention over ``keys_read`` keys in all (summed over slots AND
    layers, each layer counting what it reads: the program's ``attended`` a
    layer times the layers) with ``n_queries`` query tokens in all (slots x
    layers). Each key's K and V row is read once for its kv head's whole
    group; q is read and o written."""
    flops = 2 * 2 * keys_read * n_head * head_dim
    nbytes = 2 * keys_read * n_kv_head * head_dim * itemsize + 2 * n_queries * n_head * head_dim * itemsize
    return flops, nbytes


def routed_experts(experts_hit: int, pairs_held: int, tokens: int, hidden: int, width: int, itemsize: int):
    """The routed part of expert-layer calls: ``experts_hit`` (expert, call)
    pairs whose weights had to be read, ``pairs_held`` token-expert pairs
    computed, ``tokens`` token rows (summed over the calls) read and written."""
    flops = 2 * 3 * pairs_held * hidden * width
    nbytes = experts_hit * 3 * hidden * width * itemsize + 2 * tokens * hidden * itemsize
    return flops, nbytes
