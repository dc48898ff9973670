"""Bytes of the kernels the ``phi4flash`` configuration brings a roofline share
for, computed from shapes and from what the program counted, in
``kernel_costs.py``'s sense: the algorithm's needs, never more. Both kernels
are bound by bytes (the scan multiplies no matrix; the one-token attention
kernel does 2 FLOP a byte), so the operations are counted for the attention
alone.

- the selective scan (``ops/pallas/selective_scan.py``), one "ssm" sub-block:
  a ROW (a token of one slot) needs its ``x`` and ``dt`` read and its ``s``
  written, ``d_inner`` float32 values each, and its ``B`` and ``C``, ``d_state``
  each. A CALL ON A SLOT (a decode step's row of that slot; a chunk call)
  needs the slot's ``[d_state, d_inner]`` float32 state read and written once,
  however many rows it advances. ``A`` and ``D`` (one read a call, 0.3 MB)
  are left out.
- the paged one-token attention over head PAIRS
  (``kernel_costs_exaone_moe.paged_decode_keys`` at this family's geometry:
  ``num_key_value_heads / 2`` kv heads and ``num_attention_heads`` padded query
  heads of ``2 x head`` lanes): what the program's ``attended`` counts, a
  window layer at most its window a slot, the full layer AND each cross layer
  every key of the slot (a cross layer's query depends on the layer before it,
  so its read of the shared pages is needed, not a repeat).
"""

from __future__ import annotations

from perfbench.kernel_costs_exaone_moe import paged_decode_keys  # noqa: F401  (the reader takes it from here)


def sizes(cfg: dict) -> tuple:
    """``(d_inner, d_state)``."""
    return int(cfg.get("mamba_expand", 2)) * int(cfg["hidden_size"]), int(cfg.get("mamba_d_state", 16))


def kinds(cfg: dict) -> list:
    """Each sub-block's kind, in order (``models/phi4flash.Phi4FlashConfig.kind``)."""
    L = int(cfg["num_hidden_layers"])
    half = L // 2
    return [("ssm" if i <= half else "gmu") if i % 2 == 0 else ("attn" if i <= half + 1 else "cross") for i in range(L)]


def attending(cfg: dict) -> int:
    """Sub-blocks that read keys: the attentions and the cross-attentions."""
    return sum(k in ("attn", "cross") for k in kinds(cfg))


def pair_heads(cfg: dict) -> tuple:
    """``(kv pair heads, padded query heads, lanes of a pair)``."""
    H, KV = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return KV // 2, H, 2 * (int(cfg["hidden_size"]) // H)


def selective_scan(rows: int, slot_calls: int, d_inner: int, d_state: int) -> int:
    """Bytes of one "ssm" sub-block's scan over ``rows`` rows in ``slot_calls``
    calls on a slot."""
    return 4 * (rows * (3 * d_inner + 2 * d_state) + slot_calls * 2 * d_state * d_inner)
