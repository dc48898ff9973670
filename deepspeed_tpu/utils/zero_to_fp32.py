"""Offline consolidation: sharded training checkpoint → single fp32 file.

Analog of reference ``deepspeed/utils/zero_to_fp32.py`` (475 LoC), the script
copied into every checkpoint dir so users can recover a plain fp32
state dict from ZeRO-partitioned shards without the training cluster. Our
checkpoints are logical tensorstore arrays, so "consolidation" is a plain
CPU restore + npz write — no partition math, any host, no mesh.

CLI:
    python -m deepspeed_tpu.utils.zero_to_fp32 <ckpt_dir> <output.npz> [--tag TAG]
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import numpy as np


def _flatten_tree(tree, prefix=""):
    from .pytree import leaf_paths

    return {prefix + name: np.asarray(leaf) for name, leaf in leaf_paths(tree)}


def convert_zero_checkpoint_to_fp32_state_dict(
    ckpt_dir: str, output_file: str, tag: Optional[str] = None
) -> str:
    from ..checkpoint.deepspeed_checkpoint import DeepSpeedCheckpoint

    ck = DeepSpeedCheckpoint(ckpt_dir, tag)
    tree = ck.restore_numpy()
    params = tree["params"] if isinstance(tree, dict) and "params" in tree else getattr(tree, "params", tree)

    def to_fp32(x):
        a = np.asarray(x)
        return a.astype(np.float32) if np.issubdtype(a.dtype, np.floating) else a

    import jax

    params = jax.tree.map(to_fp32, params)
    flat = _flatten_tree(params)
    os.makedirs(os.path.dirname(os.path.abspath(output_file)) or ".", exist_ok=True)
    np.savez(output_file, **flat)
    total = sum(v.size for v in flat.values())
    print(f"saved {len(flat)} tensors ({total:,} elements) to {output_file}")
    return output_file


def get_fp32_state_dict_from_zero_checkpoint(ckpt_dir: str, tag: Optional[str] = None):
    """In-memory variant (reference get_fp32_state_dict_from_zero_checkpoint)."""
    from ..checkpoint.deepspeed_checkpoint import DeepSpeedCheckpoint

    ck = DeepSpeedCheckpoint(ckpt_dir, tag)
    tree = ck.restore_numpy()
    params = tree["params"] if isinstance(tree, dict) and "params" in tree else getattr(tree, "params", tree)
    return _flatten_tree(params)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("ckpt_dir")
    ap.add_argument("output_file")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()
    convert_zero_checkpoint_to_fp32_state_dict(args.ckpt_dir, args.output_file, args.tag)


if __name__ == "__main__":
    main()
