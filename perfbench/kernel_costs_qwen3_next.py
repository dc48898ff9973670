"""Operations and bytes of the kernels the ``qwen3_next`` configuration brings a
roofline share for, computed from shapes and from what the program counted, in
``kernel_costs.py``'s sense: the algorithm's needs, never more.

- the gated delta rule (``ops/pallas/gated_delta.py``), one "lin" sub-block. A
  CALL ON A SLOT (a decode step's row of that slot; a chunk call) reads and
  writes the slot's ``[Hv, dk, dv]`` float32 state once, however many rows it
  advances. A ROW reads its ``q``, ``k`` (``Hk x dk`` each), ``v`` (``Hv x
  dv``), ``g`` and ``beta`` (``Hv`` each) and writes its ``o`` (``Hv x dv``),
  float32. The STEP's operations a row and value head: the decay, two products
  with the state and the rank-one correction, ``8 dk dv``. The CHUNK's, a
  sub-chunk of 64 rows and value head (``c`` = 64): ``K K^T`` and ``Q K^T`` (``2
  x 2 c c dk``), the triangular solve against ``[V | K]`` (``c c (dk + dv)``:
  forward substitution's count, whatever form solves it), ``W_k S``, ``Q S``
  and the state's update (``3 x 2 c dk dv``) and ``P V'`` (``2 c c dv``).
- the routed experts (``kernel_costs_exaone_moe.routed_experts`` as it is) and
  the paged one-token attention (``paged_decode_keys`` as it is) at this
  family's geometry: 3 attention layers in 12, 2 kv heads of 256 lanes under 16
  query heads.
- :func:`decode_step_bytes`: what a decode step must move, by part, for
  ``lin_state_bytes_share``: the live slots' states in and out, the weights
  every row shares (the mixers', routers', shared experts', the head), the
  held experts that were hit, the keys attended.
"""

from __future__ import annotations

from perfbench.kernel_costs_exaone_moe import paged_decode_keys, routed_experts  # noqa: F401  (the readers take them from here)

SUB = 64   # rows of a sub-chunk (ops/pallas/gated_delta.SUB)


def kinds(cfg: dict) -> list:
    """Each sub-block's kind, in order (``models/qwen3_next.Qwen3NextConfig.kind``)."""
    n = int(cfg["full_attention_interval"])
    return ["attn" if (i + 1) % n == 0 else "lin" for i in range(int(cfg["num_hidden_layers"]))]


def heads(cfg: dict) -> tuple:
    """``(Hk, Hv, dk, dv)``."""
    return (int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"]),
            int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"]))


def state_bytes(cfg: dict) -> int:
    """A slot's float32 state, one "lin" sub-block."""
    _, Hv, dk, dv = heads(cfg)
    return 4 * Hv * dk * dv


def _row_bytes(cfg: dict) -> int:
    Hk, Hv, dk, dv = heads(cfg)
    return 4 * (2 * Hk * dk + 2 * Hv * dv + 2 * Hv)


def delta_step(rows: int, cfg: dict) -> tuple:
    """``(operations, bytes)`` of one "lin" sub-block's step over ``rows`` live
    slots (a row and a call on a slot each)."""
    _, Hv, dk, dv = heads(cfg)
    return rows * Hv * 8 * dk * dv, rows * (2 * state_bytes(cfg) + _row_bytes(cfg))


def delta_chunk(rows: int, slot_calls: int, cfg: dict) -> tuple:
    """``(operations, bytes)`` of one "lin" sub-block's chunk calls: ``rows``
    real rows in ``slot_calls`` calls (the operations a whole sub-chunk of 64
    for every 64 rows begun)."""
    _, Hv, dk, dv = heads(cfg)
    c = SUB
    per_sub = 4 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv + 2 * c * c * dv
    subs = -(-rows // c)
    return subs * Hv * per_sub, slot_calls * 2 * state_bytes(cfg) + rows * _row_bytes(cfg)


def shared_weight_bytes(cfg: dict) -> int:
    """The bf16 weights every row of a step reads whatever it picked: the
    mixers, the routers, the shared experts, the head."""
    E, V = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    Hk, Hv, dk, dv = heads(cfg)
    H, KV, D = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    lin = E * (2 * Hk * dk + 2 * Hv * dv) + E * 2 * Hv + (2 * Hk * dk + Hv * dv) * int(cfg["linear_conv_kernel_dim"]) + Hv * dv * E
    attn = E * (2 * H * D + 2 * KV * D) + H * D * E
    moe = E * int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])) + 3 * E * int(cfg["shared_expert_intermediate_size"]) + E
    k = kinds(cfg)
    return 2 * (k.count("lin") * lin + k.count("attn") * attn + len(k) * moe + E * V)


def decode_step_bytes(cfg: dict, active: int, attended: int, experts_hit: int) -> dict:
    """What one decode step must move, by part: ``active`` live slots,
    ``attended`` keys over them (one attention layer's), ``experts_hit`` held
    experts with a token (over all layers)."""
    k = kinds(cfg)
    E, F = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    kv_row = 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) * 2
    return {
        "state": k.count("lin") * active * 2 * state_bytes(cfg),
        "shared_weights": shared_weight_bytes(cfg),
        "experts": experts_hit * 3 * E * F * 2,
        "keys": k.count("attn") * attended * kv_row,
    }
