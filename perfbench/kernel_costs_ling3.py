"""Operations and bytes of the kernels the ``bailing_hybrid`` configuration
(Ling-3.0-flash) brings a roofline share for, computed from shapes and from
what the program counted, in ``kernel_costs.py``'s sense: the algorithm's
needs, never more.

- the delta rule with a decay a key channel (KDA; ``ops/pallas/gated_delta.py``'s
  ``kda_step`` / ``kda_chunk``), one "lin" sub-block. A CALL ON A SLOT reads and
  writes the slot's ``[H, dk, dv]`` float32 state once, however many rows it
  advances. A ROW reads its ``q``, ``k``, ``g`` (``H x dk`` each: the decays are
  a vector a head), ``v`` (``H x dv``) and ``beta`` (``H``) and writes its ``o``
  (``H x dv``), float32. The STEP's operations a row and head: the decay, two
  products with the state and the rank-one correction, ``8 dk dv``. The CHUNK's,
  a sub-chunk of 64 rows and head (``c`` = 64): the scalar rule's count
  (``kernel_costs_qwen3_next.delta_chunk``: ``K K^T`` and ``Q K^T``, the
  triangular solve, ``W_k S``, ``Q S``, the state's update, ``P V'``) plus the two
  ``[c, dk]`` exponentials the decays inside the sums need (``K . exp(G)`` about
  a row, and its inverse): what ANY form of the rule computes. The extra
  products of the kernel's diagonal blocks are the implementation's.
- the latent attention kernels over the LATENT layers alone (1 layer in 6) and
  the routed experts over the expert layers (``kernel_costs_mistral4`` /
  ``kernel_costs_exaone_moe`` as they are; :func:`with_mistral4_keys` hands
  their readers this file's keys under the names they read).
- :func:`decode_step_bytes`: what a decode step must move, by part, for
  ``lin_state_bytes_share``: the live slots' states in and out, the weights
  every row shares, the held experts that were hit, the latent rows attended.
"""

from __future__ import annotations

from perfbench.kernel_costs_exaone_moe import routed_experts  # noqa: F401  (the readers take them from here)
from perfbench.kernel_costs_mistral4 import latent_attention, widths  # noqa: F401

SUB = 64   # rows of a sub-chunk (ops/pallas/gated_delta.SUB)


def kinds(cfg: dict) -> list:
    """Each sub-block's kind, in order (``models/ling3.Ling3Config.kind``)."""
    n = int(cfg["layer_group_size"])
    return ["attn" if (i + 1) % n == 0 else "lin" for i in range(int(cfg["num_hidden_layers"]))]


def heads(cfg: dict) -> tuple:
    """``(H, dk, dv)``: a key head a value head, ``dk = dv = head_dim``."""
    return int(cfg["num_attention_heads"]), int(cfg["head_dim"]), int(cfg["head_dim"])


def sparse_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def state_bytes(cfg: dict) -> int:
    """A slot's float32 state, one "lin" sub-block."""
    H, dk, dv = heads(cfg)
    return 4 * H * dk * dv


def _row_bytes(cfg: dict) -> int:
    H, dk, dv = heads(cfg)
    return 4 * (3 * H * dk + 2 * H * dv + H)


def kda_step(rows: int, cfg: dict) -> tuple:
    """``(operations, bytes)`` of one "lin" sub-block's step over ``rows`` live
    slots (a row and a call on a slot each)."""
    H, dk, dv = heads(cfg)
    return rows * H * 8 * dk * dv, rows * (2 * state_bytes(cfg) + _row_bytes(cfg))


def kda_chunk(rows: int, slot_calls: int, cfg: dict) -> tuple:
    """``(operations, bytes)`` of one "lin" sub-block's chunk calls: ``rows``
    real rows in ``slot_calls`` calls (the operations a whole sub-chunk of 64
    for every 64 rows begun)."""
    H, dk, dv = heads(cfg)
    c = SUB
    per_sub = 4 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv + 2 * c * c * dv + 2 * c * dk
    return -(-rows // c) * H * per_sub, slot_calls * 2 * state_bytes(cfg) + rows * _row_bytes(cfg)


def shared_weight_bytes(cfg: dict) -> int:
    """The bf16 weights every row of a step reads whatever it picked: the
    mixers, the dense FFNs, the routers, the shared experts, the head."""
    E, V = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    H, dk, dv = heads(cfg)
    W = H * dk
    C, N, R, Vd = int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    lin = E * 4 * W + E * (W + H) + 3 * W * int(cfg["short_conv_kernel_size"]) + W * E
    attn = E * H * (N + R) + E * (C + R) + C * H * (N + Vd) + H * Vd * E + E * H
    dense = 3 * E * int(cfg["intermediate_size"])
    moe = E * int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])) + 3 * E * int(cfg["moe_shared_expert_intermediate_size"])
    k = kinds(cfg)
    first = int(cfg["first_k_dense_replace"])
    return 2 * (k.count("lin") * lin + k.count("attn") * attn + first * dense + (len(k) - first) * moe + E * V)


def decode_step_bytes(cfg: dict, active: int, attended: int, experts_hit: int) -> dict:
    """What one decode step must move, by part: ``active`` live slots,
    ``attended`` cached rows over them (one latent layer's), ``experts_hit``
    held experts with a token (over all layers)."""
    k = kinds(cfg)
    E, F = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    return {
        "state": k.count("lin") * active * 2 * state_bytes(cfg),
        "shared_weights": shared_weight_bytes(cfg),
        "experts": experts_hit * 3 * E * F * 2,
        "keys": k.count("attn") * attended * widths(cfg)[0] * 2,
    }


def with_mistral4_keys(ctx, layers: int = None):
    """``ctx`` with its configuration under the names the ``*_ms4`` readers and
    ``mla_roofline`` read: ``n_routed_experts`` the experts held, and, with
    ``layers``, ``num_hidden_layers`` that many with every one of them counted
    (the latent layers alone, for ``mla_roofline``). The other keys they read
    (``first_k_dense_replace``, ``moe_intermediate_size``, the widths) have the
    same names here."""
    import copy

    out = copy.copy(ctx)
    out.config = {**ctx.config, "n_routed_experts": ctx.config["num_experts"]}
    if layers is not None:
        out.config.update(num_hidden_layers=layers, first_k_dense_replace=0)
    return out
