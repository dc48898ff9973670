"""Operations and bytes of the two kernels the ``zaya`` configuration brings a
roofline share for, computed from shapes and from what the program counted, in
``kernel_costs.py``'s sense: the algorithm's needs, never more.

- the routed experts of an expert-layer call (``kernel_costs_exaone_moe.
  routed_experts`` as it is): the three ``hidden_size x moe_intermediate_size``
  matrices of every expert of the ``num_experts`` (16, all held) that some
  token of the call picked, the tokens' activations in and out once, and the
  products of the pairs: ONE pick a token, so a call's pairs are its tokens.
  Every layer is an expert layer.
- the paged one-token attention kernel (``kernel_costs_exaone_moe.
  paged_decode_keys`` as it is) at ``num_key_value_heads`` (2) K and V heads of
  ``head_dim`` (128) under ``num_attention_heads`` (8) query heads: each
  attended key's K and V row is read once for its kv head's four query heads.
  Every layer reads every key of a slot's context (no window): the program's
  ``attended`` a step times the layers. What CCA does before the kernel (the
  convolutions, the mean, the shift) is no part of it.
"""

from __future__ import annotations

from perfbench.kernel_costs_exaone_moe import paged_decode_keys, routed_experts  # noqa: F401  (the readers take them from here)


def sparse_layers(cfg: dict) -> int:
    """Expert layers: every layer."""
    return int(cfg["num_hidden_layers"])


def with_mistral4_keys(ctx):
    """``ctx`` with its configuration under the names the ``*_ms4`` readers
    read (``n_routed_experts``, no leading dense layer), so that those readers
    serve this file's keys as they are. The other keys they read
    (``num_hidden_layers``, ``hidden_size``, ``moe_intermediate_size``,
    ``serving``, ``dtype``) have the same names here."""
    import copy

    out = copy.copy(ctx)
    out.config = {**ctx.config, "n_routed_experts": ctx.config["num_experts"], "first_k_dense_replace": 0}
    return out
