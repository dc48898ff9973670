"""Operations and bytes of the kernels the ``longcat_flash`` configuration
brings a roofline share for, computed from shapes and from what the program
counted, in ``kernel_costs.py``'s sense: the algorithm's needs, never more.

- latent (MLA) attention, absorbed: ``kernel_costs_mistral4.latent_attention``
  as it is, at this family's widths: a (query token, cached row) pair costs,
  for each of the 64 query heads, a score over the row's 576 values and a
  value product over its first 512. The row is 576 values as the family
  defines it, NOT the 640 lanes the pool stores. A double layer attends TWICE:
  the program's counts are a cached sub-block's, and there are ``2 x
  num_layers`` of those.
- the routed experts of an expert-layer call: ``kernel_costs_exaone_moe.
  routed_experts`` as it is (the weights of the held experts that were HIT,
  the tokens' activations, the held pairs' products). The identity experts'
  pairs cost no matrix and are not in it; there is one expert layer a double
  layer.
"""

from __future__ import annotations

from perfbench.kernel_costs_exaone_moe import routed_experts  # noqa: F401  (the readers take it from here)
from perfbench.kernel_costs_mistral4 import latent_attention, widths  # noqa: F401


def with_mistral4_keys(ctx, layers: int):
    """``ctx`` with its configuration under the names the ``*_ms4`` readers and
    ``mla_roofline`` read (``num_hidden_layers`` = ``layers``, every one of them
    counted, ``moe_intermediate_size``), so that those readers serve this file's
    keys as they are. The other keys they read have the same names here."""
    import copy

    out = copy.copy(ctx)
    out.config = {**ctx.config, "num_hidden_layers": layers, "first_k_dense_replace": 0,
                  "moe_intermediate_size": ctx.config["expert_ffn_hidden_size"]}
    return out


def sub_blocks(cfg: dict) -> int:
    """Cached sub-blocks (attentions): two a double layer."""
    return 2 * int(cfg["num_layers"])


def sparse_layers(cfg: dict) -> int:
    """Expert layers: one a double layer."""
    return int(cfg["num_layers"])
